"""Pallas TPU kernel: flash attention (causal, GQA, sliding-window).

Online-softmax attention tiled for the TPU memory hierarchy:

  grid = (B, Hkv, n_q_blocks, n_kv_blocks)   # kv innermost => sequential
  q tile   (group, block_q, D) in VMEM: every q head that shares kv head h
           (group = H // Hkv), revisited across the kv dimension
  k tile   (block_k, D), v tile (block_k, Dv) in VMEM, fetched once for the
           whole group
  scratch  m, l (group, block_q, 1), acc (group, block_q, Dv) — float32 VMEM

The value head dim Dv may differ from the query/key one D (DeepSeek's latent
attention: qk 192, v 128).  The MXU consumes the (block_q, D) x (D, block_k)
logit matmul and the (block_q, block_k) x (block_k, Dv) value matmul of each
head of the group against the one K/V tile.

``flash_plan`` chooses the tile schedule from the shapes alone.  Unless the
caller names them, each block is the largest multiple of 128 up to
``MAX_BLOCK`` that divides its length rounded up to 128 (a length up to 128
is one block of itself), so no pad is larger than a 128 block's, shrunk
until the tiles fit ``VMEM_LIMIT``'s budget; a decode (Sq = 1) keeps a
one-row q block and takes a long kv block.  Few large grid steps then do the
work of many small ones, whose fixed cost had set the pace.

Tiles are classed by where the mask cuts them, on real positions only
(``_tile_class``): dead (wholly past the causal frontier, behind the sliding
window or in the kv pad), interior (no pair masked) or edge.  Dead tiles are
skipped via pl.when, giving the ~2x causal FLOP saving and the O(S*w) SWA
cost that makes mixtral's long_500k cell tractable, and are not fetched
either: the K/V index map clamps the kv block to the q block's live range
(``_kv_block``), so Pallas sees the index of the block it holds and skips
the copy.  Interior tiles run without iotas or masks; only edge tiles build
the mask.

Numerics follow the standard rescaling recurrence; -inf row-maxima (fully
masked rows, e.g. padding) are clamped so no NaN is produced.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_BLOCK = 512
# Scoped VMEM the kernel may use (a v5e core has 128 MiB); the plan keeps its
# estimate of the tiles, their double buffers and temporaries within half.
VMEM_LIMIT = 48 * 2**20


class FlashPlan(NamedTuple):
    """The forward's tile schedule for one call, and what it costs."""
    block_q: int
    block_k: int
    grid: tuple            # (B, Hkv, n_q_blocks, n_kv_blocks)
    grid_steps: int
    live_tiles: int        # grid steps that compute
    edge_tiles: int        # of them, steps that build the mask
    kv_bytes: int          # K and V bytes copied into VMEM


def _tile_class(q_lo, k_lo, *, bq, bk, q_end, kv_len, causal, window):
    """(live, inner) of the tile of queries from q_lo and keys from k_lo:
    live if the mask keeps some pair of it, inner if it keeps every pair,
    over real positions (queries up to q_end, keys below kv_len).  Only
    comparisons, & and |, so it takes ints, numpy arrays or traced scalars."""
    q_hi, k_hi = q_lo + bq - 1, k_lo + bk - 1
    live = k_lo < kv_len
    inner = k_hi < kv_len
    if causal:
        live = live & (k_lo <= q_hi) & (k_lo <= q_end)
        inner = inner & (k_hi <= q_lo)
    if window is not None:
        live = live & (k_hi > q_lo - window) & (kv_len - 1 > q_lo - window)
        inner = inner & ((k_lo > q_hi - window) | (k_lo > q_end - window))
    return live, inner


def _kv_block(iq, ik, *, bq, bk, nk, q_offset, q_end, causal, window,
              xp=jnp):
    """The kv block grid step (iq, ik) fetches: ik clamped to q block iq's
    live range, so a dead step names the block already held (the last live
    one past the causal frontier, the first one behind the window)."""
    q_lo = iq * bq + q_offset
    hi = nk - 1
    if causal:
        hi = xp.minimum(xp.minimum(q_lo + bq - 1, q_end) // bk, hi)
    ik = xp.minimum(ik, hi)
    if window is not None:
        lo = xp.maximum(q_lo - window + 1, 0) // bk
        ik = xp.minimum(xp.maximum(ik, lo), hi)
    return ik


def _block_choices(n: int) -> list:
    """Blocks for a length n, largest first: n itself up to one lane tile,
    else the multiples of 128 up to MAX_BLOCK that divide n rounded up to
    128."""
    if n <= LANES:
        return [max(n, 1)]
    padded = -(-n // LANES) * LANES
    return [b for b in range(MAX_BLOCK, 0, -LANES) if padded % b == 0]


def _vmem_bytes(bq, bk, d, dv, group, itemsize) -> int:
    """Double-buffered q, out, k and v tiles; the f32 scratch (m and l
    lane-padded); f32 copies of the tiles and the per-head score
    temporaries."""
    tiles = 2 * itemsize * (group * bq * (d + dv) + bk * (d + dv))
    scratch = 4 * group * bq * (dv + 2 * LANES)
    temps = 4 * (bk * (d + dv) + group * bq * d + 3 * group * bq * bk)
    return tiles + scratch + temps


def flash_plan(batch: int, heads: int, kv_heads: int, sq: int, sk: int,
               d: int, dv: int, *, causal: bool = True,
               window: Optional[int] = None, q_offset: int = 0,
               itemsize: int = 4, block_q: Optional[int] = None,
               block_k: Optional[int] = None) -> FlashPlan:
    """The tile schedule of ``flash_attention_pallas`` for these shapes: a
    block given is kept (cut to the length); one not given is planned, the
    larger of the two planned shrunk first while the estimate is over
    budget."""
    group = heads // kv_heads
    bqs = _block_choices(sq) if block_q is None else [min(block_q, max(sq, 1))]
    bks = _block_choices(sk) if block_k is None else [min(block_k, max(sk, 1))]
    i = j = 0
    while (_vmem_bytes(bqs[i], bks[j], d, dv, group, itemsize)
           > VMEM_LIMIT // 2 and (i + 1 < len(bqs) or j + 1 < len(bks))):
        if j + 1 == len(bks) or (i + 1 < len(bqs) and bqs[i] >= bks[j]):
            i += 1
        else:
            j += 1
    bq, bk = bqs[i], bks[j]
    nq, nk = -(-sq // bq), -(-sk // bk)
    kw = dict(q_end=q_offset + sq - 1, causal=causal, window=window)
    iq, ik = np.arange(nq)[:, None], np.arange(nk)[None, :]
    live, inner = (np.broadcast_to(t, (nq, nk)) for t in _tile_class(
        iq * bq + q_offset, ik * bk, bq=bq, bk=bk, kv_len=sk, **kw))
    fetched = np.broadcast_to(_kv_block(iq, ik, bq=bq, bk=bk, nk=nk,
                                        q_offset=q_offset, xp=np, **kw),
                              (nq, nk)).ravel()
    copies = 1 + int(np.count_nonzero(fetched[1:] != fetched[:-1]))
    per_step = batch * kv_heads
    return FlashPlan(
        block_q=bq, block_k=bk, grid=(batch, kv_heads, nq, nk),
        grid_steps=per_step * nq * nk,
        live_tiles=per_step * int(live.sum()),
        edge_tiles=per_step * int((live & ~inner).sum()),
        kv_bytes=per_step * copies * bk * (d + dv) * itemsize)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_offset: int, q_end: int, kv_len: int, bq: int, bk: int,
                  nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- tile class (absolute positions) ---------------------------------
    q_lo = iq * bq + q_offset            # first absolute query position
    k_lo = ik * bk
    live, inner = _tile_class(q_lo, k_lo, bq=bq, bk=bk, q_end=q_end,
                              kv_len=kv_len, causal=causal, window=window)

    def update(mask):
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        for j in range(q_ref.shape[1]):  # the q heads that share this kv head
            q = q_ref[0, j].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            m_prev = m_ref[j]
            if mask is not None:
                s = jnp.where(mask, s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            if mask is None:             # every row's maximum is finite
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
            else:
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m_prev),
                                 jnp.exp(m_prev - m_safe), 0.0)
            l_ref[j] = l_ref[j] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new

    @pl.when(inner)
    def _interior():
        update(None)

    @pl.when(live & jnp.logical_not(inner))
    def _edge():
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        update(mask)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "scale",
                              "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: Optional[int] = None,
                           q_offset: int = 0,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q, k: (B, H|Hkv, S, D); v: (B, Hkv, Sk, Dv). Returns (B, H, Sq, Dv).

    ``block_q``/``block_k`` None: ``flash_plan`` chooses them."""
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5

    plan = flash_plan(B, H, Hkv, Sq, Sk, D, Dv, causal=causal, window=window,
                      q_offset=q_offset, itemsize=q.dtype.itemsize,
                      block_q=block_q, block_k=block_k)
    bq, bk = plan.block_q, plan.block_k
    _, _, nq, nk = plan.grid
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))

    q_end = q_offset + Sq - 1
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, q_end=q_end, kv_len=Sk, bq=bq, bk=bk, nk=nk)
    kv_block = functools.partial(_kv_block, bq=bq, bk=bk, nk=nk,
                                 q_offset=q_offset, q_end=q_end,
                                 causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=[
            pl.BlockSpec((1, group, bq, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h, kv_block(iq, ik), 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, iq, ik: (b, h, kv_block(iq, ik), 0)),
        ],
        out_specs=pl.BlockSpec((1, group, bq, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq, :]
