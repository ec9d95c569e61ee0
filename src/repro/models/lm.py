"""Decoder-only LM over an arbitrary layer pattern (dense/MoE/SSM/hybrid).

Depth structure: optional prefix layers (e.g. deepseek's dense first layer,
run discretely in every mode, under the ``prefix_layers`` scope) followed
by ``n_repeats`` copies of the repeating ``pattern`` unit, executed with
lax.scan over stacked unit params (fast 512-device compiles).

Training modes:
  * discrete (default): standard residual stack; optional jax.checkpoint
    around each scanned unit (cfg.remat).
  * node_mode (cfg.node.mode == "node"): the paper — depth becomes ODE time,
    f(x, t) = R * (unit_{floor(tR)}(x) - x), integrated by the configured RK
    method with the configured gradient scheme (symplectic adjoint, etc.);
    the units' balance loss rides along as a scalar of the state.
    With method="euler", n_steps=R this reproduces the discrete stack
    EXACTLY (tests assert it), so the paper's memory result applies to the
    unmodified architecture.

Serving: ``mode="prefill"`` fills KV caches / SSM states and returns final
logits; ``mode="decode"`` advances one token at position ``pos``.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import SaveAt, SlicedField, as_gradient, solve
from repro.nn.common import dense_init, embed_init, no_shard, split_keys
from repro.nn.norm import init_rmsnorm, rmsnorm
from repro.runtime import scopes
from .blocks import init_layer, init_layer_cache, layer_forward


@jax.custom_jvp
def _barrier_leaves(leaves):
    return jax.lax.optimization_barrier(leaves)


@_barrier_leaves.defjvp
def _barrier_leaves_jvp(primals, tangents):
    # optimization_barrier has no differentiation rule; the barrier only
    # needs to pin the PRIMAL slices in the loop body, so tangents pass
    # through as the identity (linear, hence reverse-mode transposable).
    (leaves,), (dleaves,) = primals, tangents
    return jax.lax.optimization_barrier(leaves), dleaves


def _loop_barrier(tree):
    """Opaque identity on a scan body's sliced inputs.

    Prevents XLA from rewriting convert(slice(stack, i)) into
    slice(convert(stack), i) — i.e. hoisting dtype conversions of the
    per-layer weight/cache slices out of the loop, which would materialize
    a full-stack f32 copy (observed on the CPU backend, where bf16 dots
    lower via f32 operands)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = _barrier_leaves(leaves)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ArchConfig, dtype=jnp.float32):
    ks = split_keys(key, 6 + len(cfg.prefix))
    R = cfg.n_repeats
    params: dict = {
        "embed": embed_init(ks[0], (cfg.vocab, cfg.d_model), dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab),
                                       dtype)
    if cfg.frontend == "patch":
        params["frontend"] = dense_init(ks[2], (cfg.d_frontend, cfg.d_model),
                                        dtype)
    for i, spec in enumerate(cfg.prefix):
        params[f"prefix_{i}"] = init_layer(ks[6 + i], spec, cfg, dtype)

    def init_unit(k):
        kk = split_keys(k, len(cfg.pattern))
        return tuple(init_layer(kk[i], spec, cfg, dtype)
                     for i, spec in enumerate(cfg.pattern))

    unit_keys = jax.random.split(ks[3], R)
    params["unit"] = jax.vmap(init_unit)(unit_keys)
    return params


def init_caches(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    R = cfg.n_repeats
    prefix = [init_layer_cache(s, cfg, batch, max_len, dtype)
              for s in cfg.prefix]
    unit_one = tuple(init_layer_cache(s, cfg, batch, max_len, dtype)
                     for s in cfg.pattern)
    unit = jax.tree_util.tree_map(
        lambda l: jnp.zeros((R,) + l.shape, l.dtype), unit_one)
    return {"prefix": prefix, "unit": unit}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _unit_forward(unit_params, x, cfg: ArchConfig, *, caches=None, pos=None,
                  positions=None, shard=no_shard):
    new_caches = []
    aux = jnp.zeros((), jnp.float32)
    # multi-layer units (jamba's 8-layer block, xlstm's 8-block unit) remat
    # each LAYER too, so a unit's backward never co-materializes all its
    # layers' intermediates (nested remat composes with the scan-level one)
    per_layer_remat = cfg.remat and len(cfg.pattern) > 1 and caches is None
    for i, spec in enumerate(cfg.pattern):
        c = None if caches is None else caches[i]

        def run(lp, xx, cc, spec=spec):
            return layer_forward(lp, xx, spec, cfg, cache=cc, pos=pos,
                                 positions=positions, shard=shard)

        if per_layer_remat:
            run = jax.checkpoint(run, static_argnums=())
        x, nc, a = run(unit_params[i], x, c)
        new_caches.append(nc)
        aux = aux + a
    return x, tuple(new_caches), aux


def _embed(params, cfg, tokens, extra_embeds, shard):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "patch" and extra_embeds is not None:
        pe = extra_embeds.astype(x.dtype) @ params["frontend"]
        x = jnp.concatenate([pe, x], axis=1)
    return shard(x, ("batch", "seq", "embed"))


def _head_parts(params, cfg, x):
    with jax.named_scope(scopes.LM_LOSS):
        x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps,
                    use_pallas=cfg.use_pallas)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return x, head


def _head(params, cfg, x, shard):
    x, head = _head_parts(params, cfg, x)
    logits = (x @ head).astype(jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))


def _prefix_forward(params, cfg: ArchConfig, x, *, caches, pos, positions,
                    shard, mode):
    """The layers before the scanned units, run discretely under the
    ``prefix_layers`` scope (each checkpointed in training under remat):
    (x, new prefix caches, aux)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix = []
    with jax.named_scope(scopes.PREFIX_LAYERS):
        for i, spec in enumerate(cfg.prefix):
            c = None if caches is None else caches["prefix"][i]

            def run(lp, xx, cc, spec=spec):
                return layer_forward(lp, xx, spec, cfg, cache=cc, pos=pos,
                                     positions=positions, shard=shard)

            if cfg.remat and mode == "train":
                run = jax.checkpoint(run)
            x, nc, a = run(params[f"prefix_{i}"], x, c)
            new_prefix.append(nc)
            aux_total = aux_total + a
    return x, new_prefix, aux_total


def depth_input(params, cfg: ArchConfig, tokens, *, extra_embeds=None,
                shard=no_shard):
    """The input of the scanned units in training: the embedded tokens
    after the prefix layers, as ``lm_forward`` computes it (the ``x`` that
    ``node_depth_states`` takes)."""
    x = _embed(params, cfg, tokens, extra_embeds, shard)
    x, _, _ = _prefix_forward(params, cfg, x, caches=None, pos=None,
                              positions=jnp.arange(x.shape[1]), shard=shard,
                              mode="train")
    return x


def lm_forward(params, cfg: ArchConfig, tokens, *, caches=None, pos=None,
               extra_embeds=None, shard=no_shard, mode: str = "train",
               return_hidden: bool = False):
    """Returns {"logits", "caches", "aux"} — or, with return_hidden=True,
    {"hidden", "head", ...} so the caller can run a chunked loss without
    ever materializing the full (B, S, V) logits.

    mode: "train" (no caches), "prefill" (fill ``caches`` buffers),
    "decode" (tokens (B,1), advance caches at ``pos``)."""

    def finish(xf, caches_out, aux):
        if return_hidden:
            h, head = _head_parts(params, cfg, xf)
            return {"hidden": h, "head": head, "caches": caches_out,
                    "aux": aux}
        return {"logits": _head(params, cfg, xf, shard),
                "caches": caches_out, "aux": aux}

    x = _embed(params, cfg, tokens, extra_embeds, shard)
    S_total = x.shape[1]
    positions = jnp.arange(S_total) if pos is None else None

    x, new_prefix, aux_total = _prefix_forward(
        params, cfg, x, caches=caches, pos=pos, positions=positions,
        shard=shard, mode=mode)

    if cfg.node.mode == "node" and mode == "train":
        if not _carries_aux(cfg):
            return finish(_node_depth_solve(params, cfg, x, shard), None,
                          aux_total)
        x, aux = _node_depth_solve(params, cfg, x, shard)
        return finish(x, None, aux_total + aux)

    unit_caches = None if caches is None else caches["unit"]

    if cfg.scan_unit:
        if unit_caches is None:
            def body_nc(carry, up):
                xc, aux = carry
                up = _loop_barrier(up)
                xc, _, a = _unit_forward(up, xc, cfg, pos=pos,
                                         positions=positions, shard=shard)
                xc = shard(xc, ("batch", "seq_carry", "embed"))
                return (xc, aux + a), None

            if cfg.remat and mode == "train":
                body_nc = jax.checkpoint(body_nc)
            (x, aux_total), _ = jax.lax.scan(body_nc, (x, aux_total),
                                             params["unit"])
            new_unit = None
        else:
            def body(carry, xs):
                xc, aux = carry
                up, uc = _loop_barrier(xs)
                xc, nc, a = _unit_forward(up, xc, cfg, caches=uc, pos=pos,
                                          positions=positions, shard=shard)
                # serving (no backward): carries are not saved, so the
                # seq_carry reshard would only add an all-gather per layer
                xc = shard(xc, ("batch", "seq", "embed"))
                return (xc, aux + a), nc

            if cfg.remat and mode == "train":
                body = jax.checkpoint(body)
            (x, aux_total), new_unit = jax.lax.scan(
                body, (x, aux_total), (params["unit"], unit_caches))
    else:
        R = cfg.n_repeats
        new_unit_list = []
        for r in range(R):
            up = jax.tree_util.tree_map(lambda l: l[r], params["unit"])
            uc = None if unit_caches is None else \
                jax.tree_util.tree_map(lambda l: l[r], unit_caches)
            x, nc, a = _unit_forward(up, x, cfg, caches=uc, pos=pos,
                                     positions=positions, shard=shard)
            aux_total = aux_total + a
            new_unit_list.append(nc)
        new_unit = None if unit_caches is None else \
            jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                   *new_unit_list)

    new_caches = None
    if caches is not None:
        new_caches = {"prefix": new_prefix, "unit": new_unit}
    return finish(x, new_caches, aux_total)


# ---------------------------------------------------------------------------
# node mode: depth-time ODE over the repeat units (the paper's technique)
# ---------------------------------------------------------------------------

def _carries_aux(cfg: ArchConfig) -> bool:
    """Does a unit produce a balance loss (an expert layer)?"""
    return any(spec.ffn == "moe" for spec in cfg.pattern)


def _depth_field(cfg: ArchConfig, shard):
    """f(x, t) = R * (unit_{floor(tR)}(x) - x): depth-time vector field
    shared by the training solve and the depth-observation probe.  It
    declares the one unit it reads at time t (a ``SlicedField``), so the
    symplectic backward differentiates and accumulates that unit alone.

    Where the units produce a balance loss (``_carries_aux``) the state is
    ``(x, aux)`` and aux's field is ``R * aux_unit(x)``: euler with
    ``n_steps = R`` sums the units' losses exactly, as the discrete stack
    does."""
    R = cfg.n_repeats
    carry_aux = _carries_aux(cfg)

    def index(t):
        return jnp.clip(jnp.floor(t * R).astype(jnp.int32), 0, R - 1)

    def apply(state, t, up):
        xs = state[0] if carry_aux else state
        y, _, aux = _unit_forward(up, xs, cfg, shard=shard)
        # the symplectic adjoint SAVES the step states {x_n}; keep them
        # sequence-sharded like the discrete-mode carries
        dx = shard((y - xs) * float(R), ("batch", "seq_carry", "embed"))
        return (dx, aux * float(R)) if carry_aux else dx

    return SlicedField(index, apply)


def _initial_state(cfg: ArchConfig, x):
    return (x, jnp.zeros((), jnp.float32)) if _carries_aux(cfg) else x


def _node_depth_solve(params, cfg: ArchConfig, x, shard):
    """The final depth state: x, or (x, summed aux) where units carry one."""
    n_steps = cfg.node.n_steps or cfg.n_repeats
    return solve(_depth_field(cfg, shard), _initial_state(cfg, x),
                 params["unit"], saveat=SaveAt(t1=1.0),
                 method=cfg.node.method,
                 gradient=as_gradient(cfg.node.grad_mode),
                 stepping=n_steps,
                 backend=cfg.node.combine_backend).ys


def node_depth_states(params, cfg: ArchConfig, x, depths, shard=no_shard):
    """Observe the depth-time ODE at interior depths (probing/logit-lens).

    ``x`` is the input of the scanned units (``depth_input``).
    ``depths``: monotone observation times in (0, 1] of the depth ODE
    (depth d in [0, n_repeats] corresponds to t = d / n_repeats).  Returns
    hidden states stacked (len(depths), B, S, E) from ONE multi-observation
    solve — the whole depth trajectory costs one forward solve instead of
    one solve per probe depth, and stays differentiable under every
    grad_mode (the symplectic mode checkpoints each inter-depth segment).
    The scanned SaveAt drivers keep trace size and compile time O(1) in
    len(depths), so dense depth sweeps (a probe at every layer of a deep
    stack) compile as fast as a single observation.
    """
    n_steps = cfg.node.n_steps or cfg.n_repeats
    depths = jnp.asarray(depths)
    # per-segment step budget: keep the TOTAL grid comparable to the
    # unobserved solve's n_steps over [0, 1]
    seg_steps = max(1, -(-n_steps // depths.shape[0]))
    ys = solve(_depth_field(cfg, shard), _initial_state(cfg, x),
               params["unit"], saveat=SaveAt(ts=depths),
               method=cfg.node.method,
               gradient=as_gradient(cfg.node.grad_mode),
               stepping=seg_steps,
               backend=cfg.node.combine_backend).ys
    return ys[0] if _carries_aux(cfg) else ys
