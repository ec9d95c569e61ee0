"""Everything the harness knows about one cell, found by name.

``BENCHMARK.json`` names each cell's configuration, traffic mix, chips and
metrics.  The files behind those names:

* ``configs[i].file``             — the configuration as it is run;
* ``bench/traffic/<traffic>.json`` — the traffic mix: the parameters the
  kind's generator reads, and the limits of the correctness comparison;
* ``bench/kinds/<kind>.py``       — builds the system under test for a kind
  of configuration (the configuration file names its ``kind``), holds its
  plain reference, its control and faults, and the sizes of its CPU
  rehearsal (``SMOKE``);
* ``bench/metrics/<metric>.py``   — one reader per metric, ``read(ctx)``;
  the parts of a split metric (``<metric>.<part>``) share it.

A new cell, configuration, kind, traffic mix or metric is a new file and a
new entry; no file that is already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list       # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           wl["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    return Cell(name=name, chips=int(wl["chips"]),
                config_name=wl["config"], config=config,
                traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def kind_module(cell: Cell):
    return importlib.import_module(f"bench.kinds.{cell.config['kind']}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(ctx)`` of ``bench/metrics/<name>.py``.  A metric split by the
    cells it moves (``<quantity>.<part>``, such as ``step_mfu.cnf``) reads
    with ``<quantity>.py`` unless a file of its own full name is there."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(bench_dir, "metrics",
                            name.split(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
