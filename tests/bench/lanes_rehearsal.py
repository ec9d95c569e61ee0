"""The four-chip cells at smoke size on four virtual CPU devices, run in a
process of their own (the test's process has one device):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/lanes_rehearsal.py <cell> <dir>

builds the smoke tree under ``<dir>`` and runs the cell untraced and
traced, then once with each fault of the cell planted in its timed step
(``kind.plant``).  Prints one JSON line per run: ``{"run", "rc",
"result"}``, the result being the harness's last line."""
from __future__ import annotations

import json
import pathlib
import sys

import bench_testing as bt


def main(cell_name: str, tmp: str) -> None:
    from bench import cell as bcell
    smoke = bt.smoke_tree(pathlib.Path(tmp))
    bt.patch_chip()
    cell = bcell.load_cell(cell_name, root=str(smoke))
    kind = bcell.kind_module(cell)
    build = kind.build
    runs = [("trace0", None, 0), ("trace1", None, 1)] + [
        (fault, fault, 0) for fault in kind.faults(cell)]
    for label, fault, trace in runs:
        def broken(c, devices, seed, fault=fault):
            job = build(c, devices, seed)
            kind.plant(job, fault)
            return job
        kind.build = build if fault is None else broken
        try:
            rc, out, err = bt.run_cell(smoke, cell_name, seed=2 ** 31 + 41,
                                       trace=trace)
        finally:
            kind.build = build
        result = bt.last_line(out) if rc == 0 else err[-2000:]
        print(json.dumps({"run": label, "rc": rc, "result": result}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
