"""Shared pieces of the benchmark's CPU tests: the repository root on
``sys.path``, a smoke-size copy of the benchmark's cells, and an
in-process run of the harness with the look for a chip skipped.

The smoke tree keeps ``BENCHMARK.json``'s cells, metrics and limits and
shrinks only the sizes: a 2-layer LM of width 32 on 2 x 16 tokens, a CNF
of dim 4 on 8 samples.  Pallas kernels run in interpret mode.  The CNF's
limits are taken ten times wider: on a 4-8-8-4 field the parameter change
of the smallest leaves reads up to 1.24e-6 on the CPU, where the chip's
limit, set at full size, is 1e-6; every fault still reads 1e-3 or more.
"""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CONFIG_SMOKE = {
    "lm": {"hidden_size": 32, "intermediate_size": 64,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128,
           "use_pallas": True,
           "node": {"method": "euler", "n_steps": 2,
                    "combine_backend": "pallas"}},
    "cnf": {"dim": 4, "hidden": [8, 8], "combine_backend": "pallas",
            "rtol": 1e-5, "atol": 1e-7},
}
TRAFFIC_SMOKE = {
    "lm": {"batch": 2, "seq_len": 16, "trace_steps": 2,
           "reference": {"block_rows": 1, "loss_chunk": 8}},
    "cnf": {"batch": 8, "trace_steps": 2},
}
LIMITS_SCALE = {"lm": 1.0, "cnf": 10.0}


def smoke_tree(tmp: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark's cells at smoke sizes under ``tmp``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    kinds = {}
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        kinds[c["name"]] = cfg["kind"]
        cfg.update(CONFIG_SMOKE[cfg["kind"]])
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for w in spec["workloads"]:
        tr = json.loads((ROOT / "bench" / "traffic" /
                         (w["traffic"] + ".json")).read_text())
        kind = kinds[w["config"]]
        tr.update(TRAFFIC_SMOKE[kind])
        tr["limits"] = {k: v * LIMITS_SCALE[kind]
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
