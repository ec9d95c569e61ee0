"""AdamW with mixed-precision master weights.

Optimizer state (per param leaf): m, v in float32, plus a float32 master
copy when params are stored in bf16.  State leaves are annotated for ZeRO-1
sharding by parallel/shardings.py (sharded along the data axis on top of the
param's own tensor-parallel sharding).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.runtime import scopes


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    master_f32: bool = True


def adamw_init(params, cfg: AdamWConfig):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    state = {
        "m": jax.tree_util.tree_map(zeros, params),
        "v": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }
    # master copies only when params are reduced precision — for f32
    # params p.astype(f32) would ALIAS the param buffer (double-donation
    # crash under donate_argnums) and waste memory
    low_precision = any(l.dtype != jnp.float32
                        for l in jax.tree_util.tree_leaves(params))
    if cfg.master_f32 and low_precision:
        state["master"] = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.float32), params)
    return state


def adamw_update(params, grads, state, lr, cfg: AdamWConfig):
    with jax.named_scope(scopes.OPTIMIZER):
        step = state["step"] + 1
        b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v, master):
            g32 = g.astype(jnp.float32)
            m = cfg.b1 * m + (1 - cfg.b1) * g32
            v = cfg.b2 * v + (1 - cfg.b2) * g32 * g32
            mh = m / b1c
            vh = v / b2c
            base = master if master is not None else p.astype(jnp.float32)
            new = base - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                               + cfg.weight_decay * base)
            return new.astype(p.dtype), m, v, new

        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_g = jax.tree_util.tree_leaves(grads)
        leaves_m = jax.tree_util.tree_leaves(state["m"])
        leaves_v = jax.tree_util.tree_leaves(state["v"])
        if "master" in state:
            leaves_w = jax.tree_util.tree_leaves(state["master"])
        else:
            leaves_w = [None] * len(leaves_p)

        np_, nm, nv, nw = [], [], [], []
        for p, g, m, v, w in zip(leaves_p, leaves_g, leaves_m, leaves_v,
                                 leaves_w):
            a, b, c, d = upd(p, g, m, v, w)
            np_.append(a)
            nm.append(b)
            nv.append(c)
            nw.append(d)

        unf = treedef.unflatten
        new_state = {"m": unf(nm), "v": unf(nv), "step": step}
        if "master" in state:
            new_state["master"] = unf(nw)
        return unf(np_), new_state
