"""Rotary position embeddings (full and partial), with YaRN scaling.

YaRN (Peng et al. 2023), as DeepSeek-V2 applies it
(``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention`` of the
published modelling code): the inverse frequencies of the dims below the
low correction dim are kept, those above the high one are divided by
``factor``, and a linear ramp blends the two between; the softmax scale is
multiplied by ``mscale(factor, mscale_all_dim)**2`` and cos and sin by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YaRN:
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, base: float, y: YaRN) -> tuple:
    """(low, high): the dims between which the ramp blends."""
    def at(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = math.floor(at(y.beta_fast))
    high = math.ceil(at(y.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_softmax_scale(head_dim: int, y: YaRN | None) -> float:
    scale = head_dim ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def yarn_amplitude(y: YaRN | None) -> float:
    """The factor on cos and sin."""
    if y is None:
        return 1.0
    return (yarn_mscale(y.factor, y.mscale)
            / yarn_mscale(y.factor, y.mscale_all_dim))


def rope_freqs(head_dim: int, theta: float = 10000.0,
               rotary_dim: int | None = None, yarn: YaRN | None = None):
    rd = rotary_dim if rotary_dim is not None else head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    if yarn is None:
        return inv  # (rd/2,)
    low, high = yarn_correction_range(rd, theta, yarn)
    ramp = jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return inv / yarn.factor * ramp + inv * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freqs: jnp.ndarray,
               rotary_dim: int | None = None,
               amplitude: float = 1.0) -> jnp.ndarray:
    """x: (B, H, S, D); positions: (S,) or (B, S) absolute positions.
    Dims (2i, 2i+1) rotate together at frequency ``inv_freqs[i]``."""
    D = x.shape[-1]
    rd = rotary_dim if rotary_dim is not None else D
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * inv_freqs[None, :]
        ang = ang[None, None]                     # (1,1,S,rd/2)
    else:
        ang = positions.astype(jnp.float32)[:, None, :, None] * \
            inv_freqs[None, None, None, :]        # (B,1,S,rd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    xr = x[..., :rd].astype(jnp.float32)
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    rot = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    if rd < D:
        rot = jnp.concatenate([rot, x[..., rd:].astype(jnp.float32)], -1)
    return rot.astype(x.dtype)
