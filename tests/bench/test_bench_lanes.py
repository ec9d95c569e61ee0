"""The four-chip cell ``cnf-miniboone-lanes4`` at smoke size: a CPU
rehearsal on four virtual devices in a process of its own
(``lanes_rehearsal.py``), where it comes out correct and each fault that
the cell can have comes out not correct; and the one-chip lockstep step,
which the lanes path leaves as it was."""
import bench_testing as bt

import json
import os
import subprocess
import sys

import pytest

from test_bench_rehearsal import check_line

CELL = "cnf-miniboone-lanes4"
FAULTS = ("unchanged", "half_batch", "no_exchange")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"),
               PYTHONPATH=os.pathsep.join(
                   [str(bt.ROOT / "src"), str(bt.ROOT / "tests" / "bench"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, str(bt.ROOT / "tests" / "bench" /
                             "lanes_rehearsal.py"),
         CELL, str(tmp_path_factory.mktemp("bench_lanes"))],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {r["run"]: r for r in map(json.loads,
                                     proc.stdout.strip().splitlines())}


@pytest.mark.parametrize("trace", [0, 1])
def test_lanes_cell_on_four_devices(runs, trace):
    r = runs[f"trace{trace}"]
    assert r["rc"] == 0, r["result"]
    res = r["result"]
    assert res["device"]["count"] == 4
    check_line(res, CELL, trace)
    if trace:
        # the counters and the collectives of the sharded solve
        assert res["metrics"]["load_imbalance"]["value"] >= 1.0
        assert res["metrics"]["collective_ms_per_step"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_lanes_fault_is_not_correct(runs, fault):
    r = runs[fault]
    assert r["rc"] == 0, r["result"]
    assert r["result"]["correct"] is False, (fault, r["result"]["compared"])


def test_lockstep_step_is_the_program_of_cnf_nll(tmp_path):
    """The lockstep cell's timed step lowers to the program text of
    ``value_and_grad`` of the public ``cnf_nll`` and the AdamW update,
    built as it was before the job learned lanes."""
    import jax
    from bench import cell as bcell
    from bench.kinds import cnf
    from repro.models.cnf import CNFConfig, cnf_nll
    from repro.optim import AdamWConfig, adamw_update

    smoke = bt.smoke_tree(tmp_path)
    c = bcell.load_cell("cnf-miniboone-sym", root=str(smoke))
    job = cnf.build(c, jax.devices()[:1], 7)
    cfg, tr, opt = c.config, c.traffic, c.config["train"]
    ccfg = CNFConfig(
        dim=cfg["dim"], hidden=tuple(cfg["hidden"]),
        n_components=cfg["n_components"], t1=cfg["t1"], trace=cfg["trace"],
        method=cfg["method"], grad_mode=tr["gradient"],
        combine_backend=cfg["combine_backend"], adaptive=True,
        rtol=cfg["rtol"], atol=cfg["atol"], max_steps=cfg["max_steps"])
    adamw_cfg = AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                            weight_decay=opt["weight_decay"])

    def step(state, u, eps):
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            loss, g = jax.value_and_grad(
                lambda p, u, e: cnf_nll(p, u, e, ccfg))(state["params"],
                                                        u, eps)
            params, o = adamw_update(state["params"], g, state["opt"],
                                     opt["lr"], adamw_cfg)
        return {"params": params, "opt": o}, loss

    args = (job.state, *job._batch_of(0))
    assert job.mesh is None
    assert job.step_fn.lower(*args).as_text() == \
        jax.jit(step, donate_argnums=0).lower(*args).as_text()
