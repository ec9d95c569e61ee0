"""A run whose timed path is broken comes out not correct.

Each fault a cell can have is planted in the program's timed step
(``kind.plant``) under an otherwise whole run at smoke size, against the
limits the cell carries (the CNF's ten times wider at this size, as its
kind's ``SMOKE`` says).  The control, the program with its precision one
step below the configuration's (``kind.CONTROL``), fails too where the CPU
can lower it: bfloat16 parameters for the LM.  (XLA:CPU computes float32
matmuls exactly at every precision setting, so the CNF's control, matmuls
at "high", is read on the chip alone.)"""
import bench_testing as bt

import importlib
import json

import pytest

SPEC = json.loads((bt.ROOT / "BENCHMARK.json").read_text())
KIND = {c["name"]: json.loads((bt.ROOT / c["file"]).read_text())["kind"]
        for c in SPEC["configs"]}
CELLS = {w["name"]: (KIND[w["config"]], w["chips"]) for w in SPEC["workloads"]}
ONE_CHIP_FAULTS = [
    (cell, fault) for cell, (kind, chips) in CELLS.items() if chips == 1
    for fault in importlib.import_module(f"bench.kinds.{kind}").FAULTS]


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return bt.smoke_tree(tmp_path_factory.mktemp("bench_faults"))


def planted(monkeypatch, kind: str, fault: str):
    mod = importlib.import_module(f"bench.kinds.{kind}")
    build = mod.build

    def broken(cell, devices, seed):
        job = build(cell, devices, seed)
        mod.plant(job, fault)
        return job

    monkeypatch.setattr(mod, "build", broken)


@pytest.mark.parametrize("cell,fault", ONE_CHIP_FAULTS)
def test_planted_fault_is_not_correct(smoke, monkeypatch, cell, fault):
    bt.patch_chip(monkeypatch)
    planted(monkeypatch, CELLS[cell][0], fault)
    rc, out, err = bt.run_cell(smoke, cell, seed=123)
    assert rc == 0, err
    res = bt.last_line(out)
    assert res["correct"] is False, (fault, res["compared"])


@pytest.mark.parametrize("cell", [c for c, (k, _) in CELLS.items()
                                  if k == "lm"])
def test_lower_precision_control_is_not_correct(smoke, monkeypatch, cell):
    bt.patch_chip(monkeypatch)
    mod = importlib.import_module("bench.kinds.lm")
    build = mod.build

    def control(c, devices, seed):
        c.config = {**c.config, **mod.CONTROL}
        return build(c, devices, seed)

    monkeypatch.setattr(mod, "build", control)
    rc, out, err = bt.run_cell(smoke, cell, seed=321)
    assert rc == 0, err
    assert bt.last_line(out)["correct"] is False
