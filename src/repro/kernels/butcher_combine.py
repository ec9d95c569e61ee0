"""Pallas TPU kernels: fused Runge-Kutta stage combination.

Two variants over a stacked slope buffer ``ks`` with leading stage dim s:

  * ``butcher_combine_pallas``      — one coefficient ROW:
        out = x + h * sum_i coefs[i] * ks[i]
  * ``butcher_combine_rows_pallas`` — m rows of a Butcher matrix at once:
        out[r] = base_scale[r] * x + h * sum_i coefs[r, i] * ks[i]
    (one read of (x, ks) produces all m outputs — e.g. the step update and
    the embedded error estimate in a single pass, rows = [b; b_err] with
    base_scale = [1; 0]).

Why it matters for the paper: the RK update (Eq. 5) applies `s` AXPY chains
per step — with dopri5 that is up to 7 reads of the full state per stage
combination, repeated `N` times forward and ~3N times in the symplectic
backward pass.  The chain is purely memory-bound (arithmetic intensity
~ s FLOPs / (s+2) * 4 bytes < 1), so fusing it into one VMEM-tiled kernel
turns s+2 HBM passes into exactly one read of (x, ks) and one write of out.
The solver hot loop reaches these kernels through core/combine.py's
StageCombiner (``combine_backend="pallas"`` / "auto" on TPU).  Coefficient
rows may be traced values, not just tableau constants: the symplectic
backward recursion's h-dependent Eq. (7)/(8) rows and the SaveAt dense-
output Hermite rows (StageCombiner.interpolate, buffer [f_n, f_{n+1},
x_{n+1}-x_n]) both flow through the same single-row kernel.

Tiling: the state is reshaped to (rows, 128) lanes; each grid step processes
a (block_rows, 128) tile of x and the matching (s, block_rows, 128) tile of
ks — the (8, 128) float32 VREG layout and VMEM budget set block_rows.  The
coefficients live whole in SMEM (scalar memory) as 2-D (1, s) / (m, s)
arrays: they are per-lane data when the batched adaptive driver vmaps a
step over lanes, and vmap then adds a leading lane axis; a 1-D (s,) array
would become a (lanes, s) array whose squeezed lane block breaks Mosaic's
(8, 128) block rule, while a 2-D one keeps blocks equal to its last two
dims.

Accumulation is ``promote_types(x.dtype, float32)`` (f32 for f32/bf16
states, f64 for f64 states under x64), strictly in stage order
i = 0..s-1 — the jnp oracles in ref.py use the identical dtype and order,
so interpret-mode kernel runs match the oracles bit-for-bit (asserted in
tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _pad_to_tiles(x, ks, block_rows):
    """Flatten x/(s,)+x to lane-tiled 2-D/3-D buffers, zero-padded."""
    s = ks.shape[0]
    n = x.size
    rows = -(-n // LANE)  # ceil
    rows_pad = -(-rows // block_rows) * block_rows
    pad = rows_pad * LANE - n
    xf = jnp.pad(x.reshape(-1), (0, pad)).reshape(rows_pad, LANE)
    kf = jnp.pad(ks.reshape(s, -1), ((0, 0), (0, pad))) \
        .reshape(s, rows_pad, LANE)
    return xf, kf, rows_pad, n


def _kernel(coef_ref, x_ref, ks_ref, o_ref, *, s: int, acc_dt):
    acc = x_ref[...].astype(acc_dt)
    for i in range(s):  # unrolled: s is a small static constant (<= 13)
        acc = acc + coef_ref[0, i].astype(acc_dt) * ks_ref[i].astype(acc_dt)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def butcher_combine_pallas(x: jnp.ndarray, ks: jnp.ndarray,
                           coefs: jnp.ndarray, h: jnp.ndarray,
                           *, block_rows: int = 256,
                           interpret: bool = False) -> jnp.ndarray:
    """x: (...,); ks: (s, ...); coefs: (s,); h: scalar."""
    s = ks.shape[0]
    orig_shape = x.shape
    xf, kf, rows_pad, n = _pad_to_tiles(x, ks, block_rows)
    acc_dt = jnp.promote_types(x.dtype, jnp.float32)
    hc = (h * coefs).astype(acc_dt).reshape(1, s)

    grid = (rows_pad // block_rows,)
    out = pl.pallas_call(
        functools.partial(_kernel, s=s, acc_dt=acc_dt),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # coefs
            pl.BlockSpec((block_rows, LANE), lambda r: (r, 0)),  # x tile
            pl.BlockSpec((s, block_rows, LANE),
                         lambda r: (0, r, 0)),                   # ks tile
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANE), x.dtype),
        interpret=interpret,
    )(hc, xf, kf)
    return out.reshape(-1)[:n].reshape(orig_shape)


def _rows_kernel(coef_ref, scale_ref, x_ref, ks_ref, o_ref,
                 *, s: int, m: int, acc_dt):
    x = x_ref[...].astype(acc_dt)
    for r in range(m):  # unrolled: m is tiny (2 for update+error)
        acc = scale_ref[0, r].astype(acc_dt) * x
        for i in range(s):
            acc = acc + coef_ref[r, i].astype(acc_dt) * \
                ks_ref[i].astype(acc_dt)
        o_ref[r, :, :] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def butcher_combine_rows_pallas(x: jnp.ndarray, ks: jnp.ndarray,
                                coefs: jnp.ndarray, base_scale: jnp.ndarray,
                                h: jnp.ndarray, *, block_rows: int = 256,
                                interpret: bool = False) -> jnp.ndarray:
    """x: (...,); ks: (s, ...); coefs: (m, s); base_scale: (m,); h: scalar.

    Returns (m,) + x.shape; out[r] = base_scale[r]*x + h*sum_i coefs[r,i]*ks[i].
    """
    s = ks.shape[0]
    m = coefs.shape[0]
    orig_shape = x.shape
    xf, kf, rows_pad, n = _pad_to_tiles(x, ks, block_rows)
    acc_dt = jnp.promote_types(x.dtype, jnp.float32)
    hc = (h * coefs).astype(acc_dt)
    sc = base_scale.astype(acc_dt).reshape(1, m)

    grid = (rows_pad // block_rows,)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, s=s, m=m, acc_dt=acc_dt),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # coefs
            pl.BlockSpec(memory_space=pltpu.SMEM),               # base_scale
            pl.BlockSpec((block_rows, LANE), lambda r: (r, 0)),  # x tile
            pl.BlockSpec((s, block_rows, LANE),
                         lambda r: (0, r, 0)),                   # ks tile
        ],
        out_specs=pl.BlockSpec((m, block_rows, LANE), lambda r: (0, r, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows_pad, LANE), x.dtype),
        interpret=interpret,
    )(hc, sc, xf, kf)
    return out.reshape(m, -1)[:, :n].reshape((m,) + orig_shape)
