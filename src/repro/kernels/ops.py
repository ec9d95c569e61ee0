"""Jit'd public wrappers for the Pallas kernels with oracle fallback.

``use_pallas``: None (auto) selects the Pallas path only on TPU backends;
the pure-jnp oracle otherwise.  A forced ``use_pallas=True`` off the TPU
runs the kernel in interpret mode: this module is the only place that
chooses interpret mode, and the kernel entry points themselves default to
compiling for the TPU.

Reverse mode: ``pallas_call`` has no AD rule, so the Pallas branches of
``rms_norm`` and ``attention`` are ``jax.custom_vjp`` functions.  The
forward is the kernel and saves only its inputs (never the (Sq, Sk)
scores); the backward is the VJP of the matching jnp oracle in ``ref.py``
at those inputs, evaluated in XLA.  The gradient is therefore the gradient
of the same function the kernel computes, up to rounding, which is what
the symplectic adjoint's exactness argument needs: every gradient strategy
(symplectic, remat, backprop) sees the same VJP.  The stage-combine kernels
get their derivative rules from ``core/combine.py``'s custom JVPs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..runtime import scopes
from . import ref
from .butcher_combine import (butcher_combine_pallas,
                              butcher_combine_rows_pallas)
from .flash_attention import flash_attention_pallas
from .rmsnorm import rms_norm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas: Optional[bool]) -> bool:
    return _on_tpu() if use_pallas is None else use_pallas


def butcher_combine(x, ks, coefs, h, *, use_pallas: Optional[bool] = None):
    if _resolve(use_pallas):
        return butcher_combine_pallas(x, ks, jnp.asarray(coefs),
                                      jnp.asarray(h),
                                      interpret=not _on_tpu())
    return ref.butcher_combine_ref(x, ks, jnp.asarray(coefs), jnp.asarray(h))


def butcher_combine_rows(x, ks, coefs, base_scale, h, *,
                         use_pallas: Optional[bool] = None):
    """Multi-row stage combine: (m,)+x.shape outputs from ONE read of (x, ks)."""
    if _resolve(use_pallas):
        return butcher_combine_rows_pallas(x, ks, jnp.asarray(coefs),
                                           jnp.asarray(base_scale),
                                           jnp.asarray(h),
                                           interpret=not _on_tpu())
    return ref.butcher_combine_rows_ref(x, ks, jnp.asarray(coefs),
                                        jnp.asarray(base_scale),
                                        jnp.asarray(h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rms_norm_kernel(x, weight, residual, eps):
    return rms_norm_pallas(x, weight, residual, eps=eps,
                           interpret=not _on_tpu())


def _rms_norm_kernel_fwd(x, weight, residual, eps):
    return _rms_norm_kernel(x, weight, residual, eps), (x, weight, residual)


def _rms_norm_kernel_bwd(eps, saved, g):
    _, vjp = jax.vjp(functools.partial(ref.rms_norm_ref, eps=eps), *saved)
    return vjp(g)


_rms_norm_kernel.defvjp(_rms_norm_kernel_fwd, _rms_norm_kernel_bwd)


def rms_norm(x, weight, residual=None, *, eps: float = 1e-6,
             use_pallas: Optional[bool] = None):
    if _resolve(use_pallas):
        return _rms_norm_kernel(x, weight, residual, eps)
    return ref.rms_norm_ref(x, weight, residual, eps=eps)


def _attention_jnp(q, k, v, causal, window, q_offset, scale):
    Sq, Sk = q.shape[2], k.shape[2]
    if Sq * Sk > 2048 * 4096 and Sq >= 1024:
        # long-sequence path: query-blocked, never materializes (Sq, Sk)
        return ref.attention_blocked_ref(q, k, v, causal=causal,
                                         window=window, q_offset=q_offset,
                                         scale=scale)
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_kernel(q, k, v, causal, window, q_offset, scale):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, scale=scale,
                                  interpret=not _on_tpu())


def _attention_kernel_fwd(q, k, v, causal, window, q_offset, scale):
    out = _attention_kernel(q, k, v, causal, window, q_offset, scale)
    return out, (q, k, v)


def _attention_kernel_bwd(causal, window, q_offset, scale, saved, g):
    _, vjp = jax.vjp(
        lambda q, k, v: _attention_jnp(q, k, v, causal, window, q_offset,
                                       scale), *saved)
    return vjp(g)


_attention_kernel.defvjp(_attention_kernel_fwd, _attention_kernel_bwd)


def attention(q, k, v, *, causal: bool = True,
              window: Optional[int] = None, q_offset: int = 0,
              scale: Optional[float] = None,
              use_pallas: Optional[bool] = None):
    with jax.named_scope(scopes.ATTENTION):
        if _resolve(use_pallas):
            return _attention_kernel(q, k, v, causal, window, q_offset,
                                     scale)
        return _attention_jnp(q, k, v, causal, window, q_offset, scale)
