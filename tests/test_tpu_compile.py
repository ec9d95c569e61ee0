"""The main-path kernels compiled for a TPU v5e that is described, not attached.

The TPU compiler ships with libtpu and compiles for a topology it is only
told about, so these tests catch what interpret mode cannot: Mosaic
refusals (tiling, VMEM, dtypes), and a kernel without an AD rule under
``jax.grad``.  Nothing runs, so nothing here is a result or a time.

Only one process at a time may load libtpu, so the topology is described
inside a module-scoped fixture, never while a module is imported, and all
these tests stay in this one file.  The fixture also

* turns x64 off: Mosaic refuses the i64 values an x64 trace puts into a
  kernel, and other test modules turn x64 on for the whole process;
* turns JAX's persistent compilation cache off: a program compiled for a
  described chip can be written to it but not read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.butcher_combine import (butcher_combine_pallas,
                                           butcher_combine_rows_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rms_norm_pallas

KERNEL = "tpu_custom_call"
STATE = (8, 512, 1024)              # NODE-LM state: batch x seq x d_model
Q, KV = (8, 16, 512, 128), (8, 8, 512, 128)   # qwen3-0.6b heads at seq 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no libtpu
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            with jax.enable_x64(False):
                yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert KERNEL in text
    return text


F32 = jnp.float32


@pytest.mark.parametrize("s,dtype", [(1, F32), (7, F32), (7, jnp.bfloat16)])
def test_butcher_combine_compiles(one_chip, s, dtype):
    _compile(lambda x, ks, c, h: butcher_combine_pallas(x, ks, c, h),
             one_chip, (STATE, dtype), ((s,) + STATE, dtype), ((s,), F32),
             ((), F32))


def test_butcher_combine_rows_compiles(one_chip):
    _compile(lambda x, ks, c, b, h: butcher_combine_rows_pallas(x, ks, c, b,
                                                                h),
             one_chip, (STATE, F32), ((7,) + STATE, F32), ((2, 7), F32),
             ((2,), F32), ((), F32))


@pytest.mark.parametrize("rows", [False, True])
def test_butcher_combine_lane_vmap_compiles(one_chip, rows):
    """The batched adaptive driver vmaps a step over lanes, so the stage
    combine runs with per-lane coefficients (one h per lane)."""
    lanes, dim, s = 16, 32, 7
    if rows:
        fn = jax.vmap(lambda x, ks, c, b: butcher_combine_rows_pallas(
            x, ks, c, b, jnp.float32(1.0)), in_axes=(0, 1, 0, 0))
        _compile(fn, one_chip, ((lanes, dim), F32), ((s, lanes, dim), F32),
                 ((lanes, 2, s), F32), ((lanes, 2), F32))
    else:
        fn = jax.vmap(lambda x, ks, c: butcher_combine_pallas(
            x, ks, c, jnp.float32(1.0)), in_axes=(0, 1, 0))
        _compile(fn, one_chip, ((lanes, dim), F32), ((s, lanes, dim), F32),
                 ((lanes, s), F32))


@pytest.mark.parametrize("with_residual", [False, True])
def test_rms_norm_compiles(one_chip, with_residual):
    if with_residual:
        _compile(lambda x, w, r: rms_norm_pallas(x, w, r), one_chip,
                 (STATE, F32), ((1024,), F32), (STATE, F32))
    else:
        _compile(lambda x, w: rms_norm_pallas(x, w), one_chip,
                 (STATE, F32), ((1024,), F32))


def test_flash_attention_compiles(one_chip):
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             one_chip, (Q, F32), (KV, F32), (KV, F32))


def test_flash_attention_latent_head_dims_compile(one_chip):
    """DeepSeek-V2's latent attention at 4 x 4096: qk head 192, v head 128."""
    qk, v = (4, 16, 4096, 192), (4, 16, 4096, 128)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                                    scale=0.11472),
             one_chip, (qk, F32), (qk, F32), (v, F32))


@pytest.mark.parametrize("qk,kv,vv,scale", [
    ((8, 16, 1024, 128), (8, 8, 1024, 128), (8, 8, 1024, 128), None),
    ((4, 16, 4096, 192), (4, 16, 4096, 192), (4, 16, 4096, 128), 0.11472),
], ids=["qwen3_8x1024", "latent_4x4096"])
def test_flash_attention_planned_blocks_compile_at_cell_shapes(
        one_chip, qk, kv, vv, scale):
    """The planned blocks at the LM cells' own shapes pass Mosaic's tiling
    and VMEM checks, as one kernel named after its jitted wrapper."""
    text = _compile(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, scale=scale), one_chip, (qk, F32), (kv, F32),
        (vv, F32))
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1 and "%flash_attention_pallas" in calls[0], calls


# reverse mode through ops' custom VJPs, taking the TPU branch: the forward
# is the compiled kernel, the backward the XLA VJP of the jnp oracle


def test_rms_norm_grad_compiles(one_chip, monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def loss(x, w, r):
        return jnp.sum(ops.rms_norm(x, w, r) ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, (STATE, F32),
             ((1024,), F32), (STATE, F32))


@pytest.mark.parametrize("window", [None, 128])
def test_attention_grad_compiles(one_chip, monkeypatch, window):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def loss(q, k, v):
        return jnp.sum(ops.attention(q, k, v, causal=True, window=window)
                       ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, (Q, F32),
             (KV, F32), (KV, F32))


def test_node_lm_train_step_fits_one_chip(one_chip, monkeypatch):
    """The qwen3-0.6b NODE-mode training step at its published widths and
    depth (euler, symplectic adjoint, f32 AdamW, batch 8 x 512), with the
    Pallas kernels, compiles for one v5e and fits its 16 GiB."""
    from repro.configs import get_arch
    from repro.configs.base import NodeConfig
    from repro.train import TrainConfig, init_train_state, make_train_step

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    arch = get_arch("qwen3-0.6b").with_(use_pallas=True, node=NodeConfig(
        mode="node", method="euler", grad_mode="symplectic",
        combine_backend="pallas"))
    tcfg = TrainConfig()
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), arch, tcfg))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    batch = {k: jax.ShapeDtypeStruct((8, 512), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(arch, tcfg), donate_argnums=(0,)) \
        .lower(state, batch).compile()
    assert KERNEL in compiled.as_text()
    m = compiled.memory_analysis()
    resident = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert resident < 16 * 2**30, resident / 2**30


def test_deepseek_node_train_step_fits_one_chip(one_chip, monkeypatch):
    """DeepSeek-V2-Lite as one chip of an 8-chip expert-parallel deployment
    holds it (the dense layer and 4 expert layers, 8 of 64 routed experts,
    12,800 vocabulary rows) at its published widths, trained in NODE mode
    (euler, symplectic adjoint, f32 AdamW) on 4 x 4096 tokens: it compiles
    for one v5e, fits its 16 GiB, and builds no (S, S) score tensor."""
    from repro.configs import get_arch
    from repro.configs.base import NodeConfig
    from repro.train import TrainConfig, init_train_state, make_train_step

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    arch = get_arch("deepseek-v2-lite-16b").with_(
        n_layers=5, moe_held=(0, 8), vocab=12800, use_pallas=True,
        node=NodeConfig(mode="node", method="euler", n_steps=4,
                        grad_mode="symplectic", combine_backend="pallas"))
    tcfg = TrainConfig()
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), arch, tcfg))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    batch = {k: jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = jax.jit(make_train_step(arch, tcfg), donate_argnums=(0,)) \
        .lower(state, batch).compile()
    text = compiled.as_text()
    assert KERNEL in text
    assert "[4,16,4096,4096]" not in text
    # the compiler's peak is what the chip reads: 12.909 GiB compiled
    # against 12.959 on the chip for the qwen3 step above
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak < 15.75 * 2**30, peak / 2**30
