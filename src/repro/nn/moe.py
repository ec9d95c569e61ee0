"""Mixture-of-Experts: a capacity-factor dispatch over all experts, and a
no-drop layer that holds one chip's share of the experts.

Capacity dispatch (``moe_ffn`` with ``cfg.held`` None; Mixtral), sort-based
(MegaBlocks/MaxText style).

Routing is per sequence row (vmapped over batch), so the token sort never
crosses a data shard — under pjit the dispatch stays local to each data-
parallel shard and the only collective added by the MoE layer is the same
all-reduce a tensor-parallel dense MLP needs (expert d_ff is TP-sharded on
the ``model`` axis; ``expert`` axis sharding = EP is a config option explored
in §Perf).

Compute cost is ACTIVE-ONLY: tokens are gathered into (E, C, d) buffers
(C = capacity) and hit one batched GEMM per projection; overflow tokens are
dropped (standard capacity-factor semantics), and the auxiliary load-balance
loss (Switch/GShard) discourages overflow.

Held share (``cfg.held = (first, n)``; DeepSeek-V2).  Under expert
parallelism each chip holds ``n`` of the ``E`` routed experts.  The router
keeps its ``E`` outputs and its top-k over all of them (``moe_route``); the
layer computes its held experts' part of the result for the tokens routed
to them.  The (token, expert) assignments are sorted by expert and each
projection is one grouped matmul (``jax.lax.ragged_dot``) over the rows of
the held experts: no token is dropped, whatever the skew, and the work
follows the tokens routed to held experts.  The buffer is static, at the
most rows the held experts can receive (``T * min(k, n)``).  Shared experts
are computed for every token, as on every chip of the deployment.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.runtime import scopes
from .common import dense_init, no_shard, split_keys


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                  # per-expert hidden
    n_experts: int
    top_k: int
    n_shared: int = 0          # deepseek-style always-on shared experts
    shared_d_ff: int = 0       # hidden size of the fused shared expert
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    norm_topk_prob: bool = True    # renormalise the top-k gates to sum 1
    seq_aux: bool = False          # balance loss per sequence (DeepSeek)
    held: Optional[Tuple[int, int]] = None   # (first, n) experts held here

    @property
    def n_local(self) -> int:
        """Experts whose weights this layer holds."""
        return self.n_experts if self.held is None else self.held[1]


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = split_keys(key, 5)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_local
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        "wg": dense_init(ks[1], (E, d, f), dtype),
        "wu": dense_init(ks[2], (E, d, f), dtype),
        "wd": dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.n_shared > 0:
        sf = cfg.shared_d_ff or cfg.d_ff * cfg.n_shared
        sks = split_keys(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(sks[0], (d, sf), dtype),
            "wu": dense_init(sks[1], (d, sf), dtype),
            "wd": dense_init(sks[2], (sf, d), dtype),
        }
    return p


def _dispatch_row(x_row, gate_idx, gate_w, E: int, C: int):
    """Build gather indices for one sequence row.

    x_row: (S, d); gate_idx/gate_w: (S, k). Returns
    (slot_token: (E, C) int32 token ids or S (=dropped sentinel),
     slot_gate:  (E, C) f32 combine weights).
    """
    S, k = gate_idx.shape
    flat_e = gate_idx.reshape(-1)                       # (S*k,)
    flat_t = jnp.repeat(jnp.arange(S), k)               # token of each slot
    flat_w = gate_w.reshape(-1)
    # rank of each (token,expert) assignment within its expert, via a
    # sort + segmented-position trick: O(S*k) memory (NOT (S*k, E) one-hot)
    n = flat_e.shape[0]
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos, jnp.int32(0)))
    rank_sorted = (pos - seg_start).astype(jnp.int32)
    my_rank = jnp.zeros((n,), jnp.int32).at[sort_idx].set(rank_sorted)
    keep = my_rank < C
    slot = (flat_e.astype(jnp.int32) * C + my_rank)               # (S*k,)
    slot = jnp.where(keep, slot, E * C)                           # overflow
    slot_token = jnp.full((E * C + 1,), S, jnp.int32) \
        .at[slot].set(jnp.where(keep, flat_t.astype(jnp.int32), S)) \
        [:E * C]
    slot_gate = jnp.zeros((E * C + 1,), jnp.float32) \
        .at[slot].set(jnp.where(keep, flat_w.astype(jnp.float32),
                                0.0))[:E * C]
    return slot_token.reshape(E, C), slot_gate.reshape(E, C)


def moe_ffn(p, x, cfg: MoEConfig, *, shard=no_shard):
    """x: (B, S, d) -> (y, aux_loss)."""
    if cfg.held is not None:
        return moe_held_ffn(p, x, cfg, shard=shard)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = max(1, int(S * k * cfg.capacity_factor / E))

    logits = (x.astype(jnp.float32) @ p["router"])       # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, k)           # (B,S,k)
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, -1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (GShard/Switch) --------------------------
    me = jnp.mean(probs, axis=(0, 1))                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1))                                                   # (E,)
    aux = (cfg.router_aux_weight * E * jnp.sum(me * ce)) \
        .astype(jnp.float32)

    slot_token, slot_gate = jax.vmap(
        lambda xr, gi, gw: _dispatch_row(xr, gi, gw, E, C)
    )(x, gate_idx, gate_w)                               # (B,E,C) each

    # gather tokens (out-of-range id S clamps to row S-1, zero gate later);
    # flat per-row gather — NEVER broadcasts x to (B, E, S, d)
    ids = jnp.minimum(slot_token, S - 1).reshape(B, E * C)
    xe = jax.vmap(lambda xb, ib: jnp.take(xb, ib, axis=0))(x, ids)
    xe = xe.reshape(B, E, C, d)
    xe = shard(xe, ("batch", "experts", None, "embed"))

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["wg"])) * \
        jnp.einsum("becd,edf->becf", xe, p["wu"])
    h = shard(h, ("batch", "experts", None, "ffn"))
    ye = jnp.einsum("becf,efd->becd", h, p["wd"])        # (B,E,C,d)
    ye = ye * slot_gate[..., None].astype(ye.dtype)

    # scatter-add back to tokens
    flat = ye.reshape(B, E * C, d)
    ids = slot_token.reshape(B, E * C)
    y = jnp.zeros((B, S + 1, d), flat.dtype)
    y = jax.vmap(lambda yb, ib, fb: yb.at[ib].add(fb))(y, ids, flat)
    y = y[:, :S]

    if cfg.n_shared > 0:
        y = y + _shared_experts(p["shared"], x, shard)
    return shard(y, ("batch", "seq", "embed")), aux


def _shared_experts(sp, x, shard):
    hs = jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])
    hs = shard(hs, ("batch", "seq", "ffn"))
    return hs @ sp["wd"]


# ---------------------------------------------------------------------------
# held share: route over all experts, compute the held ones, drop nothing
# ---------------------------------------------------------------------------

class Routing(NamedTuple):
    idx: jnp.ndarray       # (B, S, k) int32 experts chosen, of all E
    gate: jnp.ndarray      # (B, S, k) float32 gates
    aux: jnp.ndarray       # () float32 balance loss


def moe_route(p, x, cfg: MoEConfig) -> Routing:
    """Softmax router over all E experts in float32 at HIGHEST (the
    published gate computes in fp32), greedy top-k; the gates renormalised
    under ``norm_topk_prob``, else the top-k scores as they are (as
    DeepSeek-V2's ``MoEGate`` at ``routed_scaling_factor`` 1).  With
    ``seq_aux`` the balance loss is DeepSeek-V2's per sequence,
    ``alpha * mean_b sum_i f_i P_i`` with ``f_i = count_i E / (S k)`` (a
    constant) and ``P_i`` the mean score over the sequence; else the
    batch-level GShard loss.  ``alpha`` is ``router_aux_weight``."""
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.top_k
    with jax.named_scope(scopes.MOE_ROUTE):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(scores, k)
        if cfg.norm_topk_prob:
            gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
        counts = jnp.zeros((B, E), jnp.float32).at[
            jnp.arange(B)[:, None], idx.reshape(B, S * k)].add(1.0)
        if cfg.seq_aux:
            f = jax.lax.stop_gradient(counts * (E / (S * k)))
            aux = jnp.mean(jnp.sum(f * jnp.mean(scores, axis=1), -1))
        else:
            ce = jnp.sum(counts, 0) / (B * S)
            aux = E * jnp.sum(jnp.mean(scores, axis=(0, 1)) * ce)
        aux = (cfg.router_aux_weight * aux).astype(jnp.float32)
    return Routing(idx.astype(jnp.int32), gate, aux)


def held_counts(route: Routing, cfg: MoEConfig) -> jnp.ndarray:
    """(n,) int32: the (token, expert) assignments each held expert got."""
    first, n = cfg.held
    local = route.idx.reshape(-1) - first
    return jnp.sum(jax.nn.one_hot(local, n, dtype=jnp.int32), axis=0)


def moe_held_ffn(p, x, cfg: MoEConfig, *, shard=no_shard):
    """The held experts' part of the layer plus the shared experts, and
    the balance loss.  x: (B, S, d) -> (y, aux)."""
    B, S, d = x.shape
    k = cfg.top_k
    first, n = cfg.held
    T = B * S
    rows = T * min(k, n)
    route = moe_route(p, x, cfg)
    with jax.named_scope(scopes.MOE_ROUTE):
        local = route.idx.reshape(T * k) - first
        held = (local >= 0) & (local < n)
        group = jnp.where(held, local, n)          # the rest sort last
        order = jnp.argsort(group, stable=True)[:rows]
        sizes = held_counts(route, cfg)
        # rows past the held ones belong to no group: the grouped matmul
        # writes neither them nor their lhs cotangent (on the TPU they keep
        # what the buffer held), so they read and write the sentinel row T,
        # zero going in and dropped coming out, and reach no token
        token = jnp.where(held[order], order // k, T)
        gate = jnp.where(held[order], route.gate.reshape(T * k)[order], 0.0)
        xf = jnp.concatenate([x.reshape(T, d), jnp.zeros((1, d), x.dtype)])
        xs = jnp.take(xf, token, axis=0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], sizes)) * \
            jax.lax.ragged_dot(xs, p["wu"], sizes)
        ye = jax.lax.ragged_dot(h, p["wd"], sizes)
    with jax.named_scope(scopes.MOE_ROUTE):
        ye = ye * gate[:, None].astype(ye.dtype)
        y = jnp.zeros((T + 1, d), ye.dtype).at[token].add(ye)
        y = y[:T].reshape(B, S, d)
    if cfg.n_shared > 0:
        y = y + _shared_experts(p["shared"], x, shard)
    return shard(y, ("batch", "seq", "embed")), route.aux
