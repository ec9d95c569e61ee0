"""Pallas TPU kernel: flash attention (causal, GQA, sliding-window).

Online-softmax attention tiled for the TPU memory hierarchy:

  grid = (B, H, n_q_blocks, n_kv_blocks)   # kv innermost => sequential
  q tile   (block_q, D)  in VMEM, revisited across the kv dimension
  k tile   (block_k, D), v tile (block_k, Dv) in VMEM, GQA-mapped:
           kv head = h // (H // Hkv)
  scratch  m (block_q,1), l (block_q,1), acc (block_q, Dv) — float32 VMEM

The value head dim Dv may differ from the query/key one D (DeepSeek's latent
attention: qk 192, v 128).  The MXU consumes the (block_q, D) x (D, block_k)
logit matmul and the (block_q, block_k) x (block_k, Dv) value matmul; block
sizes default to
128 so every matmul dimension is MXU-aligned.  Fully-masked tiles (beyond
the causal frontier or behind the sliding window) are skipped via pl.when,
giving the ~2x causal FLOP saving and the O(S*w) SWA cost that makes
mixtral's long_500k cell tractable.

Numerics follow the standard rescaling recurrence; -inf row-maxima (fully
masked rows, e.g. padding) are clamped so no NaN is produced.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_offset: int, kv_len: int, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- tile-level skip tests (absolute positions) ----------------------
    q_lo = iq * bq + q_offset            # first absolute query position
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = k_lo < kv_len
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, -jnp.inf)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev),
                         jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "scale",
                              "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: Optional[int] = None,
                           q_offset: int = 0,
                           scale: Optional[float] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jnp.ndarray:
    """q, k: (B, H|Hkv, S, D); v: (B, Hkv, Sk, Dv). Returns (B, H, Sq, Dv)."""
    B, H, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5

    bq = min(block_q, max(Sq, 1))
    bk = min(block_k, max(Sk, 1))
    sq_pad = -(-Sq // bq) * bq
    sk_pad = -(-Sk // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sk_pad - Sk), (0, 0)))

    nq = sq_pad // bq
    nk = sk_pad // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, kv_len=Sk, bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq, :]
