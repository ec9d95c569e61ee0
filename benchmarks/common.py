"""Benchmark utilities: wall-time + structural-memory measurement.

Memory on this CPU container is measured STRUCTURALLY: the compiled
artifact's live-buffer requirement (argument + output + temp - aliased
bytes from compiled.memory_analysis()).  This is exactly the quantity the
paper's Table 1/2/3 memory columns model (what must be resident during one
optimization step), and it is what a TPU deployment must fit in HBM.
"""
from __future__ import annotations

import json
import os
import time

import jax

# ---------------------------------------------------------------------------
# Machine-readable bench records (the CI perf trajectory).
#
# Every printed CSV row is also collected here; ``benchmarks.run`` dumps the
# records of each bench to BENCH_<name>.json and CI uploads them as an
# artifact, so the bench trajectory is queryable across commits instead of
# living only in job logs.  ``time_call`` additionally remembers the duration
# of its first warmup call — on a fresh function that is compile + one run,
# the compile-time proxy attached to the next ``row()`` (only when exactly
# one time_call preceded it, so the attribution is unambiguous).
# ---------------------------------------------------------------------------

_RECORDS: list = []
_LAST_FIRST_CALL_S: list = [None]
_CALLS_SINCE_ROW: list = [0]


def reset_records() -> None:
    _RECORDS.clear()
    _LAST_FIRST_CALL_S[0] = None
    _CALLS_SINCE_ROW[0] = 0


def get_records() -> list:
    return list(_RECORDS)


def record(name: str, **metrics) -> None:
    _RECORDS.append({"name": name, **metrics})


def dump_bench_json(name: str, wall_s: float, ok: bool, **extra) -> None:
    """Write ``BENCH_<name>.json``: wall time and the recorded rows."""
    payload = {"bench": name, "smoke": smoke(), "ok": ok,
               "wall_s": round(wall_s, 2), **extra, "rows": get_records()}
    path = f"BENCH_{name}.json"
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"# wrote {path} ({len(payload['rows'])} rows)", flush=True)


def smoke() -> bool:
    """True when running under ``benchmarks.run --smoke``.

    Smoke mode shrinks every benchmark to rot-check sizes (seconds, not
    minutes) so CI can execute the full driver on every push — the numbers
    are meaningless, the point is that the scripts still run.
    """
    return os.environ.get("REPRO_BENCH_SMOKE") == "1"


def time_call(fn, *args, iters: int = 3, warmup: int = 1):
    _CALLS_SINCE_ROW[0] += 1
    for i in range(warmup):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        if i == 0:
            _LAST_FIRST_CALL_S[0] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def live_bytes(jitted, *args) -> int:
    """Peak live bytes of the compiled program (structural memory)."""
    compiled = jitted.lower(*args).compile()
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def temp_bytes(jitted, *args) -> int:
    compiled = jitted.lower(*args).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def row(name: str, us_per_call: float, derived: str = "", **metrics):
    print(f"{name},{us_per_call:.1f},{derived}")
    rec = {"us_per_call": round(us_per_call, 3), "derived": derived}
    # attach the compile-time proxy (first warmup call = compile + one run)
    # ONLY when exactly one time_call preceded this row — with several
    # measurements per row the attribution would be ambiguous, so drop it.
    if _LAST_FIRST_CALL_S[0] is not None and _CALLS_SINCE_ROW[0] == 1 \
            and "compile_s" not in metrics:
        rec["compile_s"] = round(_LAST_FIRST_CALL_S[0], 4)
    _LAST_FIRST_CALL_S[0] = None
    _CALLS_SINCE_ROW[0] = 0
    rec.update(metrics)
    record(name, **rec)
