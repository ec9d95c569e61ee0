"""Every cell of BENCHMARK.json, end to end at smoke sizes on the CPU
(Pallas in interpret mode), with the look for a chip skipped: the last
line's schema, the metrics each mode reports, and ``correct``."""
import bench_testing as bt

import json

import pytest

SPEC = json.loads((bt.ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


def check_line(res: dict, cell: str, trace: int):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    kinds = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in SPEC[kinds]
            if cell in m.get("workloads", [cell])}
    got = set(res["metrics"])
    assert got <= want
    for name, m in res["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        bd = res["breakdown"]
        assert 0 < len(bd["device_ops"]) <= 10
        assert len(bd["idle_gaps"]) <= 10
    else:
        assert got == want
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return bt.smoke_tree(tmp_path_factory.mktemp("bench_smoke"))


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("trace", [0, 1])
def test_one_chip_cell(smoke, monkeypatch, cell, trace):
    bt.patch_chip(monkeypatch)
    rc, out, err = bt.run_cell(smoke, cell, seed=2 ** 31 + 17, trace=trace)
    assert rc == 0, err
    check_line(json.loads(out.strip().splitlines()[-1]), cell, trace)
    # the numbers compared close standard error
    assert err.strip().splitlines()[-1].startswith("correct ")
