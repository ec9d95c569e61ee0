"""Runtime tests: checkpoint/restore, crash-resume, elastic resharding,
gradient compression, retry logic, schedules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (CompressionConfig, compress_grads,
                         decompress_grads, cosine_schedule, wsd_schedule)
from repro.optim.compress import init_error_state
from repro.runtime import Checkpointer, RetryConfig, run_with_retries


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 8)),
                       "b": jnp.zeros(8)},
            "opt": {"m": {"w": jnp.ones((8, 8)), "b": jnp.ones(8)},
                    "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    s = _state()
    ck.save(3, s)
    restored, step = ck.restore(s)
    assert step == 3
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    s = _state()
    for step in (1, 2, 3, 4):
        ck.save(step, s)
    assert ck.list_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_async(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3, async_save=True)
    s = _state()
    ck.save(1, s, block=False)
    ck.wait()
    assert ck.latest_step() == 1


def test_checkpoint_ignores_partial(tmp_path):
    """A directory without MANIFEST (simulated crash mid-write) is not a
    valid checkpoint."""
    ck = Checkpointer(str(tmp_path))
    s = _state()
    ck.save(1, s)
    os.makedirs(tmp_path / "step_2")  # corrupt: no manifest
    (tmp_path / "step_2" / "host_0.npz").write_bytes(b"garbage")
    assert ck.latest_step() == 1
    restored, step = ck.restore(s)
    assert step == 1


def test_checkpoint_sweeps_stale_tmp_dirs(tmp_path):
    """``.tmp_step_*`` leftovers from a crash mid-write are invisible to
    restore AND swept on init / before the next save (a crash loop must
    not leak disk)."""
    ck = Checkpointer(str(tmp_path))
    s = _state()
    ck.save(1, s)
    stale = tmp_path / ".tmp_step_9_0"
    stale.mkdir()
    (stale / "host_0.npz").write_bytes(b"half-written")
    # invisible to discovery
    assert ck.list_steps() == [1]
    # a new Checkpointer (= process restart) sweeps it
    ck2 = Checkpointer(str(tmp_path))
    assert not stale.exists()
    # and a save through an EXISTING instance sweeps before writing
    stale.mkdir()
    ck2.save(2, s)
    assert not stale.exists()
    assert ck2.list_steps() == [1, 2]


def test_checkpoint_restore_rejects_wrong_leaf_count(tmp_path):
    """Restoring with a template whose pytree doesn't match what was saved
    is a clear shape-contract error, not a bare KeyError from npz."""
    ck = Checkpointer(str(tmp_path))
    s = _state()
    ck.save(1, s)
    wrong = dict(s)
    wrong["params"] = dict(s["params"], extra=jnp.zeros(3))
    with pytest.raises(ValueError, match="shape-contract mismatch"):
        ck.restore(wrong)


def test_retry_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = run_with_retries(flaky, RetryConfig(max_retries=5, backoff_s=0.0))
    assert out == "ok" and calls["n"] == 3


def test_retry_gives_up():
    def always():
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        run_with_retries(always, RetryConfig(max_retries=2, backoff_s=0.0))


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_compression_roundtrip_accuracy(mode):
    cfg = CompressionConfig(mode=mode)
    g = {"a": jax.random.normal(jax.random.PRNGKey(0), (64, 64)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (128,)) * 1e-3}
    err = init_error_state(g, cfg)
    comp, err = compress_grads(g, cfg, err)
    out = decompress_grads(comp, cfg)
    for k in g:
        rel = float(jnp.linalg.norm(out[k] - g[k]) /
                    jnp.linalg.norm(g[k]))
        assert rel < (2e-2 if mode == "bf16" else 2e-2), (k, rel)


def test_int8_error_feedback_reduces_bias():
    """With error feedback, the accumulated compressed sum converges to the
    true sum (1-bit-Adam-style argument)."""
    cfg = CompressionConfig(mode="int8", error_feedback=True)
    g = {"a": jnp.full((32,), 0.001)}
    err = init_error_state(g, cfg)
    total = jnp.zeros(32)
    for _ in range(50):
        comp, err = compress_grads(g, cfg, err)
        total = total + decompress_grads(comp, cfg)["a"]
    np.testing.assert_allclose(np.asarray(total), 0.05, rtol=0.05)


def test_schedules():
    cs = cosine_schedule(1.0, warmup=10, total=100)
    assert float(cs(jnp.int32(0))) == 0.0
    assert abs(float(cs(jnp.int32(10))) - 1.0) < 1e-6
    assert float(cs(jnp.int32(100))) < 0.2
    ws = wsd_schedule(1.0, warmup=10, stable=50, decay=40)
    assert abs(float(ws(jnp.int32(30))) - 1.0) < 1e-6
    assert float(ws(jnp.int32(100))) < 0.05


def test_elastic_reshard_cpu():
    """Restoring onto a different device layout: single-device roundtrip
    via explicit shardings (the multi-chip path is the same code)."""
    from repro.runtime import reshard_state
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    s = _state()
    specs = jax.tree_util.tree_map(lambda _: P(), s)
    out = reshard_state(s, mesh, specs)
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow   # two full driver runs with checkpoint IO
def test_train_driver_crash_resume(tmp_path):
    """End-to-end fault tolerance: run the driver with an injected failure
    and a checkpoint dir; it must complete and produce checkpoints."""
    from repro.launch import train as train_mod
    train_mod.main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "12",
                    "--global-batch", "4", "--seq-len", "32",
                    "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
                    "--fail-at-step", "7"])
    ck = Checkpointer(str(tmp_path))
    assert ck.latest_step() == 12
    # resume from the checkpoint (elastic restart path)
    train_mod.main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "14",
                    "--global-batch", "4", "--seq-len", "32",
                    "--ckpt-dir", str(tmp_path)])
    assert Checkpointer(str(tmp_path)).latest_step() == 14


_CACHE_SCRIPT = r"""
import json, jax, jax.numpy as jnp
from repro.runtime.compile_cache import enable_compile_cache
used = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"used": used,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, where set, is where the cache lands and
    the helper sets no other; otherwise it is <checkout>/.jax_cache."""
    import json
    import subprocess
    import sys

    from repro.runtime.compile_cache import DEFAULT_DIR
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(DEFAULT_DIR.parent / "src"), env.get("PYTHONPATH", "")])
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        # cache even this tiny compile, so the entry shows
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        script = _CACHE_SCRIPT
    else:
        # look at the setting only: no compile, so nothing lands in the
        # checkout's own cache
        script = _CACHE_SCRIPT.replace(
            "jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0))"
            ".block_until_ready()", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if env_dir else str(DEFAULT_DIR)
    assert out == {"used": want, "config": want}
    if env_dir:
        assert any(tmp_path.iterdir())
