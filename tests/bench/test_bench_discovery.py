"""The harness finds configurations, cells, traffic mixes and metrics by
the names in BENCHMARK.json: adding one is adding files and entries."""
import bench_testing  # noqa: F401  (puts the repository root on sys.path)

import importlib
import json
import sys

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


# A kind of configuration of its own, written as a later change would add
# it: a module with its job, reference, control, faults and smoke sizes.
TOY_KIND = '''
"""Least squares on seeded rows, one gradient step a step."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import compare

SMOKE = {"config": {"width": 3}, "traffic": {"batch": 4, "trace_steps": 1},
         "limits_scale": 1.0}
CONTROL = {}
FAULTS = ("unchanged",)


def faults(cell):
    return FAULTS


def rows(seed, i, batch, width):
    g = np.random.default_rng([seed, i])
    x = g.normal(size=(batch, width)).astype(np.float32)
    return x, x.sum(1)


def reference_readings(cfg, traffic, seed, devices=None):
    w = np.zeros(cfg["width"])
    out = {"loss": []}
    for i in range(traffic["compare_steps"]):
        x, y = rows(seed, i, traffic["batch"], cfg["width"])
        r = x.astype(np.float64) @ w - y
        out["loss"].append(float(np.mean(r * r)))
        g = 2 * x.T @ r / len(r)
        if i == 0:
            out["grad"] = {"w": float(np.linalg.norm(g))}
        w = w - cfg["lr"] * g
    out["update"] = {"w": float(np.linalg.norm(w))}
    return out


class Job:
    def __init__(self, cell, devices, seed):
        self.cfg, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.warm_steps = cell.traffic["compare_steps"]
        self.trace_steps = cell.traffic["trace_steps"]
        self.w = jnp.zeros(self.cfg["width"])
        self.readings = {"loss": []}
        lr = self.cfg["lr"]

        def step(w, x, y):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean((x @ w - y) ** 2))(w)
            return w - lr * g, loss, jnp.linalg.norm(g)

        self.step_fn = jax.jit(step)

    def step(self, i):
        x, y = rows(self.seed, i, self.traffic["batch"], self.cfg["width"])
        self.w, loss, self.gnorm = self.step_fn(self.w, x, y)
        return float(loss)

    def after_warm_step(self, i, loss):
        self.readings["loss"].append(loss)
        if i == 0:
            self.readings["grad"] = {"w": float(self.gnorm)}
        if i == self.warm_steps - 1:
            self.readings["update"] = {"w": float(jnp.linalg.norm(self.w))}
            self.readings["width"] = int(self.w.shape[0])

    def release(self):
        self.w = None

    def check(self):
        ref = reference_readings(self.cfg, self.traffic, self.seed)
        return compare.compare_training(self.readings, ref,
                                        self.traffic["limits"])


def build(cell, devices, seed):
    return Job(cell, devices, seed)


def plant(job, fault):
    inner = job.step_fn
    job.step_fn = lambda w, x, y: (w,) + tuple(inner(w, x, y)[1:])
'''


def test_a_new_kind_is_new_files_alone(tmp_path, monkeypatch):
    """A configuration of a new kind, with its module declaring ``SMOKE``,
    is found by its name and runs at its smoke sizes through the harness
    and the smoke tree as they are.  The module lies in a directory laid
    beside ``bench/kinds`` on the package's path: what a later change
    adds as a new file there."""
    import bench.kinds
    src = tmp_path / "src"
    (src / "bench" / "configs").mkdir(parents=True)
    (src / "bench" / "traffic").mkdir()
    kinds = tmp_path / "kinds"
    kinds.mkdir()
    (kinds / "toy_lstsq.py").write_text(TOY_KIND)
    monkeypatch.setattr(bench.kinds, "__path__",
                        [*bench.kinds.__path__, str(kinds)])
    (src / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "kind": "toy_lstsq", "width": 1000, "lr": 0.1}))
    (src / "bench" / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"batch": 4096, "compare_steps": 3, "trace_steps": 3,
         "limits": {"loss_gap": 1e-5, "grad_gap": 1e-5,
                    "update_gap": 1e-5}}))
    spec = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": [{"name": "train_step_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    (src / "BENCHMARK.json").write_text(json.dumps(spec))
    try:
        smoke = bench_testing.smoke_tree(tmp_path / "smoke", src=src)
        cfg = json.loads((smoke / "bench" / "configs" / "toy.json")
                         .read_text())
        tr = json.loads((smoke / "bench" / "traffic" / "toy-mix.json")
                        .read_text())
        assert (cfg["width"], tr["batch"], tr["trace_steps"]) == (3, 4, 1)
        built = []
        toy = importlib.import_module("bench.kinds.toy_lstsq")
        build = toy.build
        monkeypatch.setattr(toy, "build",
                            lambda *a: built.append(build(*a)) or built[-1])
        bench_testing.patch_chip(monkeypatch)
        rc, out, err = bench_testing.run_cell(smoke, "toy-cell", seed=5)
        assert rc == 0, err
        res = bench_testing.last_line(out)
        assert res["correct"] is True, res["compared"]
        assert set(res["metrics"]) == {"train_step_ms", "setup_s"}
        assert built[0].readings["width"] == 3
    finally:
        sys.modules.pop("bench.kinds.toy_lstsq", None)


def test_every_kind_has_what_the_harness_and_its_tests_use():
    spec = json.loads((bench_testing.ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        kind = json.loads((bench_testing.ROOT / c["file"]).read_text())[
            "kind"]
        mod = importlib.import_module(f"bench.kinds.{kind}")
        for name in ("SMOKE", "CONTROL", "FAULTS", "faults", "plant",
                     "build", "reference_readings"):
            assert hasattr(mod, name), (kind, name)
        assert {"config", "traffic", "limits_scale"} <= set(mod.SMOKE)
        assert set(mod.FAULTS) <= set(mod.faults(bcell.load_cell(
            next(w["name"] for w in spec["workloads"]
                 if w["config"] == c["name"]))))
