"""Attention blocks: GQA (with qk-norm, sliding window, partial rope), MLA.

Shapes: activations (B, S, d_model); heads layout (B, H, S, Dh) internally.
KV caches: GQA -> {"k": (B, Smax, Hkv, Dh), "v": ...};
           MLA -> {"ckv": (B, Smax, kv_lora), "kr": (B, Smax, rope_dim)}
(the MLA cache stores the *compressed* latent — the paper-faithful memory win
of DeepSeek-V2 — and decode uses the absorbed-matmul formulation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from .common import dense_init, no_shard, split_keys
from .norm import init_rmsnorm, rmsnorm
from .rope import (YaRN, apply_rope, rope_freqs, yarn_amplitude,
                   yarn_softmax_scale)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    window: Optional[int] = None
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0           # stablelm uses 0.25
    # MLA (deepseek) fields
    mla: bool = False
    kv_lora: int = 512
    q_lora: int = 0                    # 0 = no q compression (v2-lite)
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_scaling: Optional[YaRN] = None


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = split_keys(key, 6)
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], (d, H * Dh), dtype),
        "wk": dense_init(ks[1], (d, Hkv * Dh), dtype),
        "wv": dense_init(ks[2], (d, Hkv * Dh), dtype),
        "wo": dense_init(ks[3], (H * Dh, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(Dh, dtype)
        p["k_norm"] = init_rmsnorm(Dh, dtype)
    return p


def gqa_attention(p, x, cfg: AttnConfig, *, positions=None, cache=None,
                  pos=None, shard=no_shard, use_pallas=None,
                  causal: bool = True):
    """x: (B, S, d). Training/prefill when cache is None or being filled;
    decode when ``pos`` (scalar int) is given with S == 1.

    Returns (out, new_cache_or_None).
    """
    B, S, d = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rd = int(Dh * cfg.rotary_pct)
    inv = rope_freqs(Dh, cfg.rope_theta, rd)

    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, Hkv, Dh)
    v = (x @ p["wv"]).reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, use_pallas=use_pallas)
        k = rmsnorm(p["k_norm"], k, use_pallas=use_pallas)
    q = q.transpose(0, 2, 1, 3)   # (B,H,S,Dh)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q = shard(q, ("batch", "heads", "seq", "head_dim"))
    k = shard(k, ("batch", "kv_heads", "seq", "head_dim"))

    if pos is None:
        pp = positions if positions is not None else jnp.arange(S)
        q = apply_rope(q, pp, inv, rd)
        k = apply_rope(k, pp, inv, rd)
        new_cache = None
        if cache is not None:  # prefill: write into the cache buffer
            cache_axes = ("batch", "seq_carry", "cache_heads", "head_dim")
            new_cache = {
                "k": shard(jax.lax.dynamic_update_slice(
                    cache["k"], k.transpose(0, 2, 1, 3).astype(
                        cache["k"].dtype), (0, 0, 0, 0)), cache_axes),
                "v": shard(jax.lax.dynamic_update_slice(
                    cache["v"], v.transpose(0, 2, 1, 3).astype(
                        cache["v"].dtype), (0, 0, 0, 0)), cache_axes),
            }
        out = kops.attention(q, k, v, causal=causal, window=cfg.window,
                             q_offset=0, use_pallas=use_pallas)
    else:
        # decode: S == 1, append to cache at index ``pos``
        ppos = jnp.reshape(pos, (1,))
        q = apply_rope(q, ppos, inv, rd)
        k = apply_rope(k, ppos, inv, rd)
        z = jnp.zeros((), dtype=jnp.asarray(pos).dtype)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype),
            (z, pos, z, z))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype),
            (z, pos, z, z))
        new_cache = {"k": ck, "v": cv}
        # decode: no head-repeat, no f32 cache copy (see ref docstring)
        out = kref.decode_attention_ref(q, ck, cv, pos, window=cfg.window)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
    out = out @ p["wo"]
    return shard(out, ("batch", "seq", "embed")), new_cache


def init_gqa_cache(cfg: AttnConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, Hkv, Dh), dtype),
            "v": jnp.zeros((batch, max_len, Hkv, Dh), dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = split_keys(key, 8)
    d, H = cfg.d_model, cfg.n_heads
    qd = cfg.nope_head_dim + cfg.rope_head_dim
    p = {
        "wq": dense_init(ks[0], (d, H * qd), dtype),
        "wdkv": dense_init(ks[1], (d, cfg.kv_lora), dtype),
        "kv_norm": init_rmsnorm(cfg.kv_lora, dtype),
        "wuk": dense_init(ks[2], (cfg.kv_lora, H * cfg.nope_head_dim), dtype),
        "wuv": dense_init(ks[3], (cfg.kv_lora, H * cfg.v_head_dim), dtype),
        "wkr": dense_init(ks[4], (d, cfg.rope_head_dim), dtype),
        "wo": dense_init(ks[5], (H * cfg.v_head_dim, d), dtype),
    }
    return p


def mla_attention(p, x, cfg: AttnConfig, *, positions=None, cache=None,
                  pos=None, shard=no_shard, use_pallas=None):
    """DeepSeek-V2 MLA.  Training and prefill expand the latent into
    per-head K (qk head dim nope + rope) and V (v head dim) and run the
    flash kernel; decode runs the absorbed formulation against the
    compressed cache.  RoPE on the rope dims, YaRN-scaled where the config
    says."""
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    inv = rope_freqs(dr, cfg.rope_theta, dr, yarn=cfg.rope_scaling)
    amp = yarn_amplitude(cfg.rope_scaling)
    scale = yarn_softmax_scale(dn + dr, cfg.rope_scaling)

    q = (x @ p["wq"]).reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ckv = rmsnorm(p["kv_norm"], x @ p["wdkv"], use_pallas=use_pallas)
    kr = (x @ p["wkr"]).reshape(B, S, 1, dr).transpose(0, 2, 1, 3)

    if pos is None:
        pp = positions if positions is not None else jnp.arange(S)
        q_rope = apply_rope(q_rope, pp, inv, amplitude=amp)
        kr = apply_rope(kr, pp, inv, amplitude=amp)
        k_nope = (ckv @ p["wuk"]).reshape(B, S, H, dn).transpose(0, 2, 1, 3)
        v = (ckv @ p["wuv"]).reshape(B, S, H, dv).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr, (B, H, S, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        qq = shard(qq, ("batch", "heads", "seq", "head_dim"))
        out = kops.attention(qq, k, v, causal=True, q_offset=0, scale=scale,
                             use_pallas=use_pallas)
        new_cache = None
        if cache is not None:
            new_cache = {
                "ckv": shard(jax.lax.dynamic_update_slice(
                    cache["ckv"], ckv.astype(cache["ckv"].dtype),
                    (0, 0, 0)), ("batch", "seq_carry", "embed")),
                "kr": shard(jax.lax.dynamic_update_slice(
                    cache["kr"],
                    kr[:, 0].astype(cache["kr"].dtype), (0, 0, 0)),
                    ("batch", "seq_carry", "head_dim")),
            }
    else:
        ppos = jnp.reshape(pos, (1,))
        q_rope = apply_rope(q_rope, ppos, inv, amplitude=amp)
        kr = apply_rope(kr, ppos, inv, amplitude=amp)
        z = jnp.zeros((), dtype=jnp.asarray(pos).dtype)
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (z, pos, z)),
            "kr": jax.lax.dynamic_update_slice(
                cache["kr"], kr[:, 0].astype(cache["kr"].dtype),
                (z, pos, z)),
        }
        C = new_cache["ckv"].astype(jnp.float32)          # (B, Smax, dl)
        KR = new_cache["kr"].astype(jnp.float32)          # (B, Smax, dr)
        # absorbed: q_eff[h] = wuk[h]^T q_nope[h]  -> attend over latent
        wuk = p["wuk"].reshape(cfg.kv_lora, H, dn).astype(jnp.float32)
        q_abs = jnp.einsum("bhsd,lhd->bhsl", q_nope.astype(jnp.float32),
                           wuk)                            # (B,H,1,dl)
        s_lat = jnp.einsum("bhsl,btl->bhst", q_abs, C)
        s_rot = jnp.einsum("bhsd,btd->bhst", q_rope.astype(jnp.float32), KR)
        s = (s_lat + s_rot) * scale
        kpos = jnp.arange(C.shape[1])[None, None, None, :]
        s = jnp.where(kpos <= pos, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        lat = jnp.einsum("bhst,btl->bhsl", pr, C)          # (B,H,1,dl)
        wuv = p["wuv"].reshape(cfg.kv_lora, H, dv).astype(jnp.float32)
        out = jnp.einsum("bhsl,lhd->bhsd", lat, wuv).astype(x.dtype)

    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    out = out @ p["wo"]
    return shard(out, ("batch", "seq", "embed")), new_cache


def init_mla_cache(cfg: AttnConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
            "kr": jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype)}
