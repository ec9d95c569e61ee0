"""Readings that the limits of the correctness check are set from.

    python3 -m bench.calibrate --workloads <cell>[,<cell>...] --seeds 1,2,3 \\
        [--control-seeds N] [--out PATH]

For every seed and cell: the program's first steps as a run takes them
(set-up and warm steps of the cell's job, no window), then the plain
reference, and the numbers compared.  For the first ``--control-seeds``
seeds also the control (the program with its precision knob one step
below the configuration's, ``kind.CONTROL``) and each fault the first
cell can have (``kind.faults``) planted in the program's timed step
(``kind.plant``).
The cells given together share one reference, so they must differ only
in their gradient strategy.

Benchmark runs never run this; the limits in ``bench/traffic/*.json`` are
set from its output as ``PERF.md`` records.  One process, as a run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

from bench import compare
from bench.cell import kind_module, load_cell


def numbers(prog: dict, ref: dict) -> dict:
    limits = {"loss_gap": 0.0, "grad_gap": 0.0, "update_gap": 0.0}
    return {k: {"value": v["value"], "where": v["where"]}
            for k, v in compare.compare_training(prog, ref, limits).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from bench.run import enable_compile_cache
    from bench import device as bdevice
    enable_compile_cache(jax)
    cells = [load_cell(w) for w in args.workloads.split(",")]
    devices = bdevice.require_tpu(jax.devices(), max(c.chips for c in cells))
    seeds = [int(s) for s in args.seeds.split(",")]
    kind = kind_module(cells[0])
    out = {"device": devices[0].device_kind, "rows": []}

    def readings(cell, seed, fault=None, label="program"):
        """The job's readings, or None where the run fails (a control
        that crashes has failed the check)."""
        t0 = time.perf_counter()
        job = None
        try:
            job = kind.build(cell, devices[:cell.chips], seed)
            if fault is not None:
                kind.plant(job, fault)
            for i in range(job.warm_steps):
                job.after_warm_step(i, job.step(i))
            return job.readings
        except Exception as e:  # noqa: BLE001  (recorded, then went on)
            print(f"[calibrate] seed {seed} {cell.name} {label} failed: "
                  f"{type(e).__name__}: {str(e)[:300]}", file=sys.stderr)
            return None
        finally:
            if job is not None:
                job.release()
            print(f"[calibrate] seed {seed} {cell.name} {label} "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr,
                  flush=True)

    for n, seed in enumerate(seeds):
        progs = {cell.name: readings(cell, seed) for cell in cells}
        extra = {}
        if n < args.control_seeds:
            ctl = dataclasses.replace(cells[0], config={**cells[0].config,
                                                        **kind.CONTROL})
            extra["control"] = readings(ctl, seed, label="control")
            for fault in kind.faults(cells[0]):
                extra[fault] = readings(cells[0], seed, fault, fault)
        t0 = time.perf_counter()
        ref = kind.reference_readings(cells[0].config, cells[0].traffic,
                                      seed, devices[:cells[0].chips])
        row = {"seed": seed, "ref_loss": ref["loss"],
               "reference_s": time.perf_counter() - t0}
        for name, prog in {**progs, **extra}.items():
            row[name] = None if prog is None else numbers(prog, ref)
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
