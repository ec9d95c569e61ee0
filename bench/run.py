"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's system under test from the seed (weights on the
device in one jitted call, the compiled step with its state) and drives it
through its first steps, which compile and which the correctness check
reads.  The window then drives the same object for ``--seconds``.  With
``--trace 1`` a short profiled window follows and the per-layer metrics are
read from it.  After the windows the program's state is freed and the
cell's plain reference decides ``correct``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``, then ``compared``); the numbers compared, each beside its
limit, are also the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run exits with code 2 and prints
no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as fh:
            boot = next(int(l.split()[1]) for l in fh if l.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


T_START = _process_start()

from bench import device as bdevice  # noqa: E402
from bench import trace as btrace  # noqa: E402
from bench.cell import (ROOT, kind_module, load_cell,  # noqa: E402
                        metric_reader)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: object
    job: object
    devices: list
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    memory_peak_bytes: int = 0
    trace: object = None          # bench.trace.Trace of the traced window
    trace_steps: int = 0


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``; every program is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_step(jax, job, i: int) -> float:
    with jax.profiler.TraceAnnotation(btrace.STEP_SPAN):
        return job.step(i)


def read_metrics(entries, ctx) -> dict:
    out = {}
    for m in entries:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, root)
    import jax
    try:
        devices = bdevice.require_tpu(jax.devices(), cell.chips)
    except bdevice.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    ctx = Context(cell=cell, job=None, devices=devices,
                  peaks=bdevice.peaks(devices[0].device_kind))

    job = kind_module(cell).build(cell, devices, args.seed)
    ctx.job = job
    losses = []
    for i in range(job.warm_steps):
        losses.append(run_step(jax, job, i))
        job.after_warm_step(i, losses[-1])
    # one more step after the readings, whose programs and buffers the
    # window does not use: the device's memory settles before the window
    losses.append(run_step(jax, job, job.warm_steps))
    # set-up leaves millions of objects from tracing and compiling; kept out
    # of the collector, a full collection in the window scans none of them
    gc.collect()
    gc.freeze()
    ctx.setup_s = time.time() - T_START

    i = job.warm_steps + 1
    t0 = time.perf_counter()
    ends = []
    while True:
        losses.append(run_step(jax, job, i))
        i += 1
        ctx.steps += 1
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= args.seconds:
            break
    ctx.window_s = ends[-1] - t0
    steps_ms = sorted(1000.0 * (b - a) for a, b in zip([t0] + ends, ends))
    print(f"window {ctx.steps} steps, ms: least {steps_ms[0]!r} median "
          f"{steps_ms[len(steps_ms) // 2]!r} most {steps_ms[-1]!r}",
          file=sys.stderr)
    ctx.memory_peak_bytes = bdevice.peak_bytes(devices)
    for d in devices:
        print(f"memory {d} {bdevice.memory_readings(d)}", file=sys.stderr)

    dev = bdevice.describe(devices, ctx.memory_peak_bytes)
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(tdir):
                for _ in range(job.trace_steps):
                    losses.append(run_step(jax, job, i))
                    i += 1
            ctx.trace = btrace.extract(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx.trace_steps = job.trace_steps
        dev["busy_s"] = btrace.busy_seconds(ctx.trace)
        dev["window_s"] = btrace.window_seconds(ctx.trace)
        metrics = read_metrics(cell.per_layer, ctx)
    else:
        metrics = read_metrics(cell.end_to_end, ctx)

    window_losses = losses[job.warm_steps + 1:]
    failed = sum(not math.isfinite(x) for x in window_losses)
    gc.unfreeze()
    job.release()
    compared = job.check()
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": len(window_losses),
              "failed": failed, "metrics": metrics, "device": dev}
    if ctx.trace is not None:
        result["breakdown"] = btrace.breakdown(ctx.trace)
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
