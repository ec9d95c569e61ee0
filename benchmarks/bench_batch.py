"""Batch-native adaptive solving: masked per-lane control vs lockstep.

Workload: a heterogeneous-stiffness batch of B linear oscillators

    dx/dt = omega_b * [x_1, -x_0],   omega_b log-spaced over ~1.5 decades,

integrated with adaptive dopri5.  The stiffness omega rides in the state
with zero dynamics, so one shared parameter pytree serves every lane.  The
closed-form solution (a rotation by omega_b * t) gives an exact per-lane
accuracy reference.

Three ways to solve the batch:

  * lockstep  — batch-in-state, ``solve(...)`` with no batch_axis: ONE
                controller, error norm pooled (RMS) over the whole batch.
                Every lane takes the same accepted grid, every controller
                f-eval evaluates all B lanes, and the per-lane tolerance is
                NOT enforced — the pooled norm dilutes the stiff lane by
                ~sqrt(B), so its realized error exceeds rtol.
  * masked    — ``solve(..., batch_axis=0)``: per-lane controllers in one
                fused while_loop.  Easy lanes land early and stop paying
                (useful) f-evals; every lane meets its own tolerance.
  * vmap      — ``jax.vmap`` of the single-trajectory solve: semantically
                per-lane too, but JAX's while_loop batching rule selects
                the ENTIRE carry (including the max_steps checkpoint
                buffers) on every trial step — the wall-time gap to the
                masked driver is the cost of those whole-buffer selects.

Reported per row: steady-state wall time, total per-trajectory f-evals
(masked/vmap: sum over lanes of each lane's count; lockstep: B x the shared
controller's count — each of its f-evals evaluates every lane), and the
worst per-lane max-abs error against the closed form.  The acceptance
number is fevals_total: masked needs measurably fewer trajectory-evals
than lockstep on a heterogeneous batch (docs/batching.md quotes the
recorded BENCH_bench_batch.json).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import (AdaptiveConfig, DirectBackprop, SaveAt,
                        SymplecticAdjoint, solve)
from .common import row, smoke, time_call

# NOTE: deliberately f32 — run.py executes every bench in one process, so
# flipping jax_enable_x64 here would leak into the other benches (only
# bench_tolerance is a command of its own).  Tolerances below sit above f32 noise.


def field(state, t, params):
    x, om = state
    dx = params["gain"] * om[..., None] * jnp.stack(
        [x[..., 1], -x[..., 0]], axis=-1)
    return (dx, jnp.zeros_like(om))


PARAMS = {"gain": jnp.float32(1.0)}


def exact(x0, om, t):
    c, s = jnp.cos(om * t), jnp.sin(om * t)
    rot = jnp.stack([jnp.stack([c, s], -1), jnp.stack([-s, c], -1)], -2)
    return jnp.einsum("bij,bj->bi", rot, x0)


def _setup(B, span):
    om = jnp.logspace(0.0, span, B)
    x0 = jax.random.normal(jax.random.PRNGKey(0), (B, 2))
    x0 = x0 / jnp.linalg.norm(x0, axis=-1, keepdims=True)
    return x0, om


def run_one(B, span, t1, cfg):
    x0, om = _setup(B, span)
    state = (x0, om)
    ref = exact(x0, om, t1)

    def err_worst(ys):
        return float(jnp.max(jnp.abs(ys[0] - ref)))

    sv = dict(saveat=SaveAt(t1=t1), method="dopri5",
              gradient=DirectBackprop(), stepping=cfg)

    masked = jax.jit(lambda s: solve(field, s, PARAMS, batch_axis=0, **sv))
    lockstep = jax.jit(lambda s: solve(field, s, PARAMS, **sv))
    vmapped = jax.jit(jax.vmap(lambda s: solve(field, s, PARAMS, **sv)))

    sol_m = masked(state)
    fe_masked = int(jnp.sum(sol_m.stats["n_fevals"]))
    us = time_call(masked, state) * 1e6
    row(f"bench_batch/masked_B{B}", us, f"fevals={fe_masked}",
        B=B, fevals_total=fe_masked,
        fevals_max_lane=int(jnp.max(sol_m.stats["n_fevals"])),
        err_worst=err_worst(sol_m.ys))

    sol_l = lockstep(state)
    # every controller f-eval evaluates the full batch width
    fe_lockstep = B * int(sol_l.stats["n_fevals"])
    us = time_call(lockstep, state) * 1e6
    row(f"bench_batch/lockstep_B{B}", us, f"fevals={fe_lockstep}",
        B=B, fevals_total=fe_lockstep, err_worst=err_worst(sol_l.ys))

    sol_v = vmapped(state)
    fe_vmap = int(jnp.sum(sol_v.stats["n_fevals"]))
    us = time_call(vmapped, state) * 1e6
    row(f"bench_batch/vmap_singles_B{B}", us, f"fevals={fe_vmap}",
        B=B, fevals_total=fe_vmap, err_worst=err_worst(sol_v.ys))

    # symplectic-adjoint gradient: per-lane backward replay vs lockstep
    def loss(s, batch_axis):
        sol = solve(field, s, PARAMS, saveat=SaveAt(t1=t1), method="dopri5",
                    gradient=SymplecticAdjoint(), stepping=cfg,
                    batch_axis=batch_axis)
        return jnp.sum((sol.ys[0] - ref) ** 2)

    for name, ax in (("grad_masked", 0), ("grad_lockstep", None)):
        g = jax.jit(jax.grad(lambda s: loss(s, ax)))
        us = time_call(g, state) * 1e6
        row(f"bench_batch/{name}_B{B}", us, "", B=B)

    print(f"#   B={B}: fevals masked {fe_masked} vs lockstep {fe_lockstep} "
          f"({fe_lockstep / max(fe_masked, 1):.2f}x); worst lane err "
          f"masked {err_worst(sol_m.ys):.2e} vs lockstep "
          f"{err_worst(sol_l.ys):.2e}", flush=True)


def main():
    if smoke():
        cfg = AdaptiveConfig(rtol=1e-5, atol=1e-8, max_steps=256,
                             max_attempts=8192, initial_step=0.05)
        run_one(B=4, span=1.0, t1=1.0, cfg=cfg)
        return
    cfg = AdaptiveConfig(rtol=1e-5, atol=1e-8, max_steps=1024,
                         max_attempts=16384, initial_step=0.05)
    for B in (8, 32):
        run_one(B=B, span=1.5, t1=2.0, cfg=cfg)


if __name__ == "__main__":
    main()
