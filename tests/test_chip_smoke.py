"""chip_smoke.py rehearsed on the CPU at smoke sizes.

The script has no CPU mode: its device check raises off the TPU, and its
kernel and device-memory checks read what only a TPU produces.  These tests
patch exactly those three functions (and the sizes) from the test side, then
run ``main`` end to end, so a change to an entry point that would break the
chip run breaks here first.  The compile cache helper is patched out too,
so the test leaves JAX's global cache settings alone.
"""
import importlib.util
import json
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def chip_smoke(monkeypatch):
    from repro.runtime import compile_cache
    mod = _load_chip_smoke()
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "(not set by this test)")
    monkeypatch.setattr(mod, "require_tpu", lambda jax: None)
    monkeypatch.setattr(mod, "assert_kernels", lambda text, what: None)
    monkeypatch.setattr(mod, "peak_bytes", lambda jax: 0)
    return mod


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_chip_smoke_refuses_cpu():
    mod = _load_chip_smoke()
    with pytest.raises(RuntimeError, match="no TPU"):
        mod.require_tpu(jax)


def test_chip_smoke_main_phases_on_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "TRAIN_ARGV", [
        "--arch", "qwen3-0.6b", "--smoke", "--grad-mode", "symplectic",
        "--node-method", "euler", "--steps", "3", "--global-batch", "2",
        "--seq-len", "16"])
    monkeypatch.setattr(chip_smoke, "EXACT_SMOKE", True)
    monkeypatch.setattr(chip_smoke, "EXACT_BATCH", 2)
    monkeypatch.setattr(chip_smoke, "EXACT_SEQ", 16)
    monkeypatch.setattr(chip_smoke, "SERVE_ARGV", ["ode", "--smoke"])
    with jax.enable_x64(False):     # the script never turns x64 on
        chip_smoke.main([])
    out = capsys.readouterr().out
    assert "[train] step 2: loss" in out
    assert "relative L2 gap symplectic vs backprop" in out
    assert "8/8 requests succeeded" in out
    last = _last_json(out)
    assert last["ok"] is True
    assert last["device"] == {"platform": "cpu",
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}


_FOUR_CHIPS = r"""
import importlib.util, json
from repro.runtime import compile_cache
compile_cache.enable_compile_cache = lambda: "(not set by this test)"
spec = importlib.util.spec_from_file_location("chip_smoke", {path!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.require_tpu = lambda jax: None
mod.SHARD_LANES, mod.SHARD_DIM = 16, 4
mod.main(["--four-chips"])
"""


def test_chip_smoke_four_chips_on_host_devices(run_sharded):
    out = run_sharded(_FOUR_CHIPS.format(path=str(ROOT / "chip_smoke.py")),
                      devices=4)
    assert "ys sharded over 4 devices" in out
    last = _last_json(out)
    assert last["ok"] is True and last["device"]["count"] == 4
