"""Mesh-sharded masked-batch solving: throughput scaling over host devices.

Workload: the heterogeneous-stiffness oscillator batch of bench_batch at
B=64 (omega log-spaced over ~1.5 decades, SORTED — so contiguous lane
blocks have genuinely different step-count demands), solved with adaptive
dopri5 three ways:

  * 1dev     — ``solve(..., batch_axis=0)``: the single-device masked
               per-lane driver.  Its fused while_loop runs until the
               SLOWEST lane of the whole batch finishes, evaluating all B
               lanes every trip.
  * sharded  — ``solve(..., batch_axis=0, mesh=(D,)-data mesh)``: each
               shard's while_loop stops at its OWN slowest lane, so easy
               shards retire early AND the shards run on separate devices.
  * grad     — same pair under ``jax.grad`` (symplectic adjoint), since
               training throughput is the quantity the paper cares about.

Reported per row: steady-state wall time, trajectories/s, the measured
speedup vs 1dev, the cross-shard ``load_imbalance`` metric (max/mean
per-shard accepted steps — 1.0 is perfectly balanced; the sorted-stiffness
batch is deliberately NOT), and ``ideal_speedup`` — the trip-count model
``B * max_lane_steps / (lanes_per_shard * max_shard_steps)``: what D-way
sharding buys when the devices are real cores (measured wall speedup
approaches it on a multi-core host; on a single-core container the forced
host devices serialize and the measured number reflects only the wasted-
work reduction).

A command of its own (the device flag must precede jax init, so
``benchmarks.run`` does not run it):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.bench_shard [--smoke]

writes BENCH_bench_shard.json.
"""
from __future__ import annotations

import os
import sys
import time

# must happen before jax initializes its backend; harmless if the parent
# already set a device count (standalone CI invocation does).
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp

from repro.core import AdaptiveConfig, SaveAt, solve
from .common import dump_bench_json, row, smoke, time_call


def field(state, t, params):
    x, om = state
    dx = params["gain"] * om[..., None] * jnp.stack(
        [x[..., 1], -x[..., 0]], axis=-1)
    return (dx, jnp.zeros_like(om))


PARAMS = {"gain": jnp.float32(1.0)}


def _setup(B, span=1.2):
    om = jnp.logspace(0.0, span, B)          # sorted: blocks differ in cost
    x0 = jax.random.normal(jax.random.PRNGKey(0), (B, 2))
    x0 = x0 / jnp.linalg.norm(x0, axis=-1, keepdims=True)
    return (x0, om)


def main() -> None:
    from repro.launch.mesh import make_lane_mesh
    B = 16 if smoke() else 64
    saveat = SaveAt(t1=1.0 if smoke() else 4.0)
    cfg = AdaptiveConfig(rtol=1e-5, atol=1e-7,
                         max_steps=128 if smoke() else 1024)
    state0 = _setup(B)
    devices = len(jax.devices())
    iters = 2 if smoke() else 5

    def solve_ys(x, mesh=None):
        kw = {"mesh": mesh} if mesh is not None else {}
        sol = solve(field, x, PARAMS, stepping=cfg, t0=0.0, batch_axis=0,
                    saveat=saveat, **kw)
        return sol.ys

    def loss(x, mesh=None):
        ys = solve_ys(x, mesh)
        return jnp.sum(ys[0] ** 2)

    base = jax.jit(solve_ys)
    s_base = time_call(base, state0, iters=iters)
    row("shard/value/1dev", s_base * 1e6, f"B={B}",
        trajectories_per_s=round(B / s_base, 1), devices=1)

    gbase = jax.jit(jax.grad(loss))
    s_gbase = time_call(gbase, state0, iters=iters)
    row("shard/grad/1dev", s_gbase * 1e6, f"B={B}",
        trajectories_per_s=round(B / s_gbase, 1), devices=1)

    if devices < 2:
        print("# only 1 device visible (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8): "
              "skipping sharded rows")
        return

    mesh = make_lane_mesh((devices,))
    # stats pass (unjitted once): per-shard accepted steps + imbalance
    sol = solve(field, state0, PARAMS, stepping=cfg, batch_axis=0,
                saveat=saveat, mesh=mesh)
    n_steps = jax.device_get(sol.stats["n_steps"])
    shard_steps = jax.device_get(sol.stats["shard_steps"])
    imbalance = float(sol.stats["load_imbalance"])
    lanes_per_shard = B // devices
    # trip-count model: fused loops cost trips x lanes evaluated per trip
    work_1dev = int(n_steps.max()) * B
    work_shard = int(shard_steps.max() // lanes_per_shard + 1) \
        * lanes_per_shard
    ideal = work_1dev / max(work_shard, 1)

    sharded = jax.jit(lambda x: solve_ys(x, mesh))
    s_shard = time_call(sharded, state0, iters=iters)
    row("shard/value/sharded", s_shard * 1e6,
        f"B={B} D={devices}",
        trajectories_per_s=round(B / s_shard, 1), devices=devices,
        speedup=round(s_base / s_shard, 2),
        ideal_speedup=round(ideal, 2),
        load_imbalance=round(imbalance, 3),
        shard_steps=[int(s) for s in shard_steps])

    gshard = jax.jit(lambda x: jax.grad(loss)(x, mesh))
    s_gshard = time_call(gshard, state0, iters=iters)
    row("shard/grad/sharded", s_gshard * 1e6, f"B={B} D={devices}",
        trajectories_per_s=round(B / s_gshard, 1), devices=devices,
        speedup=round(s_gbase / s_gshard, 2))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    if "--smoke" in sys.argv:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    enable_compile_cache()
    t_start = time.time()
    main()
    dump_bench_json("bench_shard", time.time() - t_start, True,
                    devices=len(jax.devices()))
