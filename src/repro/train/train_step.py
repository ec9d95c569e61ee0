"""Training step factory: loss -> grad (any mode) -> compress -> clip ->
AdamW, with optional microbatch gradient accumulation.

One factory serves every assigned architecture: decoder LMs (dense / MoE /
SSM / hybrid), the VLM (patch-embedding prefix), and the enc-dec audio model.
The gradient scheme is selected by the arch config's NodeConfig.grad_mode —
a registered strategy name or a ``repro.core.GradientStrategy`` instance
(the paper's ``SymplecticAdjoint`` being the headline mode); the LM forward
resolves it through ``repro.core.solve`` (core/api.py), so a newly
registered strategy is trainable here with zero changes to this factory.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.encdec import decode_forward, encode, init_encdec
from repro.models.lm import init_lm, lm_forward
from repro.nn.common import no_shard
from repro.optim import (AdamWConfig, CompressionConfig, adamw_init,
                         adamw_update, clip_by_global_norm, compress_grads,
                         decompress_grads)
from repro.optim.compress import init_error_state
from .losses import IGNORE, lm_loss, lm_loss_chunked
from .state import TrainState, init_solver_stats, node_solver_counts


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    max_grad_norm: float = 1.0
    microbatches: int = 1
    adamw: AdamWConfig = AdamWConfig()
    compression: CompressionConfig = CompressionConfig()
    param_dtype: str = "float32"
    # chunked cross-entropy: never materialize (B, S, V) logits.
    # 0 disables (full-logits path, kept for ablation).
    loss_chunk: int = 512


def init_train_state(key, arch: ArchConfig, tcfg: TrainConfig) -> TrainState:
    """Fresh ``TrainState`` — the full checkpoint contract (see state.py)."""
    init_key, train_key = jax.random.split(key)
    dtype = jnp.dtype(tcfg.param_dtype)
    if arch.encdec:
        params = init_encdec(init_key, arch, dtype)
    else:
        params = init_lm(init_key, arch, dtype)
    return TrainState(
        params=params, opt=adamw_init(params, tcfg.adamw), rng=train_key,
        data_step=jnp.zeros((), jnp.int32),
        solver_stats=init_solver_stats(),
        compress_err=init_error_state(params, tcfg.compression))


def _forward_loss(params, batch, arch: ArchConfig, shard,
                  loss_chunk: int = 512):
    rh = loss_chunk > 0
    if arch.encdec:
        memory = encode(params, batch["frames"], arch, shard=shard)
        out = decode_forward(params, arch, batch["tokens"], memory=memory,
                             shard=shard, mode="train", return_hidden=rh)
        labels = batch["labels"]
    elif arch.frontend == "patch":
        out = lm_forward(params, arch, batch["tokens"],
                         extra_embeds=batch["patch_embeds"], shard=shard,
                         mode="train", return_hidden=rh)
        P = batch["patch_embeds"].shape[1]
        pad = jnp.full(batch["labels"].shape[:1] + (P,), IGNORE,
                       batch["labels"].dtype)
        labels = jnp.concatenate([pad, batch["labels"]], axis=1)
    else:
        out = lm_forward(params, arch, batch["tokens"], shard=shard,
                         mode="train", return_hidden=rh)
        labels = batch["labels"]
    if rh:
        loss = lm_loss_chunked(out["hidden"], out["head"], labels,
                               loss_chunk)
    else:
        loss = lm_loss(out["logits"], labels)
    return loss + out["aux"], loss


def make_grad_fn(arch: ArchConfig, tcfg: TrainConfig, shard=no_shard):
    """``(params, batch) -> (grads, total_loss, ce_loss)`` for one batch:
    the gradient a train step feeds its optimizer, under the arch's
    gradient strategy."""
    def grads_of(params, batch):
        def lf(p):
            return _forward_loss(p, batch, arch, shard, tcfg.loss_chunk)
        (total, ce), grads = jax.value_and_grad(lf, has_aux=True)(params)
        return grads, total, ce

    return grads_of


def make_train_step(arch: ArchConfig, tcfg: TrainConfig,
                    lr_fn: Optional[Callable] = None, shard=no_shard,
                    grad_constraint: Optional[Callable] = None):
    """``grad_constraint`` (optional): pytree->pytree hook applied to the
    gradients before the optimizer — the launcher passes a ZeRO-2-style
    data-axis sharding constraint here, which turns the DP gradient
    all-reduce into a reduce-scatter and divides gradient residency by the
    DP degree (the optimizer update runs sharded; XLA all-gathers the
    updated params, completing the ZeRO-1 flow)."""
    if lr_fn is None:
        lr_fn = lambda step: jnp.asarray(tcfg.lr, jnp.float32)  # noqa: E731

    grads_of = make_grad_fn(arch, tcfg, shard)

    # static forward-solve cost of one train step (NODE archs; see state.py)
    solve_steps, solve_fevals = node_solver_counts(arch)
    n_solves = max(tcfg.microbatches, 1)

    def train_step(state, batch):
        params = state["params"]
        if tcfg.microbatches > 1:
            def mb(carry, mbatch):
                g_acc, l_acc = carry
                g, total, _ = grads_of(params, mbatch)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), g_acc, g)
                if grad_constraint is not None:
                    # keep the f32 accumulator ZeRO-sharded across steps
                    g_acc = grad_constraint(g_acc)
                return (g_acc, l_acc + total), None

            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((tcfg.microbatches,
                                     x.shape[0] // tcfg.microbatches)
                                    + x.shape[1:]), batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if grad_constraint is not None:
                zeros = grad_constraint(zeros)
            (grads, loss_sum), _ = jax.lax.scan(mb, (zeros, 0.0), mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / tcfg.microbatches, grads)
            loss = loss_sum / tcfg.microbatches
        else:
            grads, loss, _ = grads_of(params, batch)

        # gradient compression across the DP all-reduce boundary
        err = state.get("compress_err")
        comp, new_err = compress_grads(grads, tcfg.compression, err)
        grads = decompress_grads(comp, tcfg.compression)
        if grad_constraint is not None:
            grads = grad_constraint(grads)

        grads, gnorm = clip_by_global_norm(grads, tcfg.max_grad_norm)
        lr = lr_fn(state["opt"]["step"])
        params, opt = adamw_update(params, grads, state["opt"], lr,
                                   tcfg.adamw)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        if isinstance(state, TrainState):
            # advance every contract field: split the rng stream (the step
            # key is reserved for stochastic layers), bump the data cursor,
            # accumulate the static solve counters
            rng, _step_key = jax.random.split(state.rng)
            stats = {
                "n_steps": state.solver_stats["n_steps"]
                + jnp.int32(solve_steps * n_solves),
                "n_fevals": state.solver_stats["n_fevals"]
                + jnp.int32(solve_fevals * n_solves)}
            return TrainState(params=params, opt=opt, rng=rng,
                              data_step=state.data_step + 1,
                              solver_stats=stats,
                              compress_err=new_err), metrics
        new_state = {"params": params, "opt": opt}
        if new_err is not None:
            new_state["compress_err"] = new_err
        return new_state, metrics

    return train_step
