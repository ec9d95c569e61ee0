"""The harness finds configurations, cells, traffic mixes and metrics by
the names in BENCHMARK.json: adding one is adding files and entries."""
import bench_testing  # noqa: F401  (puts the repository root on sys.path)

import json

from bench import cell as bcell


def test_new_cell_config_and_metric_are_found_without_edits(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "kind": "lm", "hidden_size": 8}))
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps({"batch": 3, "limits": {}}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx * 2\n")
    spec = {
        "configs": [{"name": "new-model",
                     "file": "bench/configs/new-model.json"}],
        "workloads": [{"name": "new-cell", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "other", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "new_metric", "unit": "%",
                       "workloads": ["new-cell"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bcell.load_cell("new-cell", root=str(tmp_path))
    assert cell.config["hidden_size"] == 8
    assert cell.traffic["batch"] == 3
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    read = bcell.metric_reader("new_metric",
                               bench_dir=str(tmp_path / "bench"))
    assert read(21) == 42


def test_every_named_file_of_the_benchmark_exists():
    root = bench_testing.ROOT
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = bcell.load_cell(w["name"])
        assert (root / "bench" / "kinds" /
                (cell.config["kind"] + ".py")).is_file()
        assert cell.chips == w["chips"]
        for m in cell.end_to_end + cell.per_layer:
            assert (root / "bench" / "metrics" /
                    (m["name"].split(".")[0] + ".py")).is_file(), m["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bcell.metric_reader(m["name"]))


def test_unknown_cell_is_an_error():
    import pytest
    with pytest.raises(KeyError, match="no workload"):
        bcell.load_cell("no-such-cell")


def test_parts_of_a_split_metric_share_its_reader(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "rate.py").write_text(
        "def read(ctx):\n    return 'shared'\n")
    assert bcell.metric_reader("rate.cnf", bench_dir=str(tmp_path))(0) \
        == "shared"
    # a part with a file of its own reads with that file
    (tmp_path / "metrics" / "rate.lm.py").write_text(
        "def read(ctx):\n    return 'own'\n")
    assert bcell.metric_reader("rate.lm", bench_dir=str(tmp_path))(0) \
        == "own"
    assert bcell.metric_reader("rate", bench_dir=str(tmp_path))(0) \
        == "shared"
