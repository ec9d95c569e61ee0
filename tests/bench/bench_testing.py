"""Shared pieces of the benchmark's CPU tests: the repository root on
``sys.path``, a smoke-size copy of the benchmark's cells, and an
in-process run of the harness with the look for a chip skipped.

The smoke tree keeps ``BENCHMARK.json``'s cells, metrics and limits and
shrinks only the sizes, as each kind of configuration declares them
(``SMOKE`` in ``bench/kinds/<kind>.py``: keys laid over the configuration
and the traffic, and a scale of the limits).  Pallas kernels run in
interpret mode.
"""
from __future__ import annotations

import contextlib
import importlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def smoke_tree(tmp: pathlib.Path, src: pathlib.Path = ROOT) -> pathlib.Path:
    """A copy under ``tmp`` of the benchmark at ``src`` with every cell at
    the smoke sizes of its kind (``bench/kinds/<kind>.py: SMOKE``), where
    ``SMOKE["per_traffic"][<traffic>]`` overrides for one traffic mix."""
    spec = json.loads((src / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    smoke = {}
    for c in spec["configs"]:
        cfg = json.loads((src / c["file"]).read_text())
        smoke[c["name"]] = importlib.import_module(
            f"bench.kinds.{cfg['kind']}").SMOKE
        cfg.update(smoke[c["name"]]["config"])
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        tr = json.loads((src / "bench" / "traffic" /
                         (w["traffic"] + ".json")).read_text())
        sizes = smoke[w["config"]]
        sizes = {**sizes, **sizes.get("per_traffic", {}).get(w["traffic"],
                                                            {})}
        tr.update(sizes["traffic"])
        tr["limits"] = {k: v * sizes["limits_scale"]
                        for k, v in tr["limits"].items()}
        (tmp / "bench" / "traffic" / (w["traffic"] + ".json")).write_text(
            json.dumps(tr))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def patch_chip(monkeypatch=None):
    """Skip the harness's look for a chip: any device passes, the peaks
    are nominal, the memory counter reads 0 and the compile cache stays
    off."""
    from bench import device, run
    fake = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    items = [(device, "require_tpu", lambda devs, chips: list(devs)[:chips]),
             (device, "peaks", lambda kind, path=None: fake),
             (device, "memory_readings", lambda dev: {"peak": 0}),
             (run, "enable_compile_cache", lambda jax: None)]
    for mod, name, value in items:
        if monkeypatch is None:
            setattr(mod, name, value)
        else:
            monkeypatch.setattr(mod, name, value)


def run_cell(root, name: str, seed: int = 11, trace: int = 0,
             seconds: float = 0.5):
    """``bench.run.main`` in this process: (exit code, stdout, stderr)."""
    from bench import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=str(root))
    return rc, out.getvalue(), err.getvalue()


def last_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])
