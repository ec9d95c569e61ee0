"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the interpret-mode kernel tests and the
implementations off the TPU, where Mosaic kernels do not lower; the
attention oracles are also the backward of the kernels' custom VJPs
(``ops.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def butcher_combine_ref(x: jnp.ndarray, ks: jnp.ndarray,
                        coefs: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """x + h * sum_i coefs[i] * ks[i].

    x: (...,), ks: (s, ...), coefs: (s,). The RK stage-combination hot loop
    (Eq. 5) fused into a single HBM pass.  Accumulates in
    promote_types(x.dtype, float32) — >= f32 for low-precision states, f64
    for f64 states — strictly in stage order: the exact dtype and sequence
    the Pallas kernel executes, so interpret-mode kernel runs match this
    oracle bit-for-bit.
    """
    acc_dt = jnp.promote_types(x.dtype, jnp.float32)
    hc = (h * coefs).astype(acc_dt)
    acc = x.astype(acc_dt)
    for i in range(ks.shape[0]):
        acc = acc + hc[i] * ks[i].astype(acc_dt)
    return acc.astype(x.dtype)


def butcher_combine_rows_ref(x: jnp.ndarray, ks: jnp.ndarray,
                             coefs: jnp.ndarray, base_scale: jnp.ndarray,
                             h: jnp.ndarray) -> jnp.ndarray:
    """Multi-row combine: out[r] = base_scale[r]*x + h*sum_i coefs[r,i]*ks[i].

    x: (...,), ks: (s, ...), coefs: (m, s), base_scale: (m,).  Returns
    (m,) + x.shape.  Same promote_types(x.dtype, f32) stage-order
    accumulation as the Pallas kernel (bit-for-bit in interpret mode).
    """
    acc_dt = jnp.promote_types(x.dtype, jnp.float32)
    hc = (h * coefs).astype(acc_dt)
    sc = base_scale.astype(acc_dt)
    xf = x.astype(acc_dt)
    outs = []
    for r in range(coefs.shape[0]):
        acc = sc[r] * xf
        for i in range(ks.shape[0]):
            acc = acc + hc[r, i] * ks[i].astype(acc_dt)
        outs.append(acc.astype(x.dtype))
    return jnp.stack(outs)


def rms_norm_ref(x: jnp.ndarray, weight: jnp.ndarray,
                 residual: Optional[jnp.ndarray] = None,
                 eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm with optional fused residual add (pre-norm transformer).

    Returns normed output; if residual is given the normalization input is
    (x + residual) — the standard fused pre-norm pattern.
    """
    xf = x.astype(jnp.float32)
    if residual is not None:
        xf = xf + residual.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def _masked_softmax(s):
    m = jnp.max(s, axis=-1, keepdims=True)
    # rows that are fully masked (all -inf) produce zeros, not NaNs
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(jnp.isfinite(s), jnp.exp(s - m), 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  *, causal: bool = True,
                  window: Optional[int] = None,
                  q_offset: int = 0,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Multi-head attention with GQA, causal masking and sliding window.

    q: (B, H, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv); H % Hkv == 0.
    ``q_offset`` is the absolute position of q[..., 0, :] (decode: Sk - Sq).
    window w: query j attends keys i with j - w < i <= j (SWA, mixtral-style).
    """
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    Sk = k.shape[2]
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = _masked_softmax(s)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, pos,
                         *, window: Optional[int] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token GQA decode attention against a cache in ITS OWN dtype.

    q: (B, H, 1, D); k_cache, v_cache: (B, Smax, Hkv, D) (bf16 typically);
    pos: scalar int (absolute position of the new token).  No head repeat
    and no f32 copy of the cache — scores/output use f32 ACCUMULATION via
    preferred_element_type while the cache tensor stays bf16 (flash-
    decoding numerics).  Padding/future keys masked with kpos <= pos.
    """
    B, H, _, D = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(k_cache.shape[1])[None, None, None, :]
    mask = kpos <= pos
    if window is not None:
        mask &= kpos > pos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = _masked_softmax(s)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, 1, D).astype(q.dtype)


def attention_blocked_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          *, causal: bool = True,
                          window: Optional[int] = None,
                          q_offset: int = 0,
                          scale: Optional[float] = None,
                          block_q: int = 512) -> jnp.ndarray:
    """Query-blocked attention: identical math to attention_ref but never
    materializes the full (Sq, Sk) score matrix — peak live is
    (block_q, Sk) per (batch, head).  This is the long-sequence pure-JAX
    path used by the dry-run (the Pallas flash kernel is the TPU path);
    each block is rematerialized in backward (jax.checkpoint).
    """
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    if Sq % bq != 0:
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)
    kk = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    Sk = k.shape[2]
    kpos = jnp.arange(Sk)[None, :]
    nblocks = Sq // bq
    qb = q.reshape(B, H, nblocks, bq, D).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def one_block(args):
        qi, i = args
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32),
                       kk) * scale
        qpos = (i * bq + jnp.arange(bq))[:, None] + q_offset
        mask = jnp.ones((bq, Sk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = _masked_softmax(s)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    def body(_, args):
        return None, one_block(args)

    _, ob = jax.lax.scan(body, None, (qb, jnp.arange(nblocks)))
    out = ob.transpose(1, 2, 0, 3, 4).reshape(B, H, Sq, v.shape[-1])
    return out.astype(q.dtype)

