"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows AND writes one
``BENCH_<name>.json`` per bench (wall time + every recorded row with its
steady-state / compile-time metrics) so the perf trajectory is a queryable
artifact, not just job logs.  CI uploads ``BENCH_*.json`` from the
``--smoke`` job on every push.

  bench_steps           -> Fig. 2  (memory vs number of steps)
  bench_orders          -> Table 1 (memory scaling orders in N, s, L)
  bench_cnf             -> Table 2 (CNF: NLL / memory / time per grad method)
  bench_rk_sweep        -> Table 3 (RK methods s=2,3,6,12)
  bench_physics         -> Table 4 (KdV / Cahn-Hilliard, dopri8)
  bench_combine         -> fused vs unfused stage combination (StageCombiner)
  bench_saveat_compile  -> SaveAt compile time vs observation count
  bench_batch           -> masked per-lane batching vs lockstep (batch_axis)
  bench_serve           -> continuous-batching engine vs sequential solving
  bench_checkpoint      -> blocking vs async checkpoint save stall, overlap
                           with compute, restore throughput (docs/training.md)
  roofline              -> EXPERIMENTS.md roofline (reads runs/dryrun.jsonl)

Usage:
    python -m benchmarks.run [--smoke] [bench_name]

``--smoke`` sets REPRO_BENCH_SMOKE=1 so every benchmark runs at tiny
rot-check sizes (CI executes this on every push; see .github/workflows).

This driver runs every bench in this one process and starts no child
process: a chip belongs to the one process that touched JAX first.  Two
benches need a process configured differently, so each is a command of
its own that writes its own ``BENCH_<name>.json``:

    python -m benchmarks.bench_tolerance [--smoke]   # Fig. 1; turns on x64
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.bench_shard [--smoke]  # mesh-sharded lanes vs
                                                     # 1 device
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from . import common


def main() -> None:
    args = sys.argv[1:]
    if "--smoke" in args:
        args.remove("--smoke")
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        print("# smoke mode: rot-check sizes, numbers are meaningless",
              flush=True)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_batch, bench_checkpoint, bench_cnf, bench_combine,
                   bench_orders, bench_physics, bench_rk_sweep,
                   bench_saveat_compile, bench_serve, bench_steps, roofline)

    benches = [
        ("bench_steps", bench_steps.main),
        ("bench_orders", bench_orders.main),
        ("bench_cnf", bench_cnf.main),
        ("bench_rk_sweep", bench_rk_sweep.main),
        ("bench_physics", bench_physics.main),
        ("bench_combine", bench_combine.main),
        ("bench_saveat_compile", bench_saveat_compile.main),
        ("bench_batch", bench_batch.main),
        ("bench_serve", bench_serve.main),
        ("bench_checkpoint", bench_checkpoint.main),
        ("roofline", roofline.main),
    ]
    only = args[0] if args else None
    failed = []
    for name, fn in benches:
        if only and only != name:
            continue
        print(f"# === {name} ===", flush=True)
        common.reset_records()
        t0 = time.time()
        ok = True
        try:
            fn()
        except Exception:  # noqa: BLE001
            ok = False
            failed.append(name)
            traceback.print_exc()
        wall = time.time() - t0
        print(f"# {name} done in {wall:.1f}s", flush=True)
        common.dump_bench_json(name, wall, ok)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
