"""Per-architecture smoke tests (reduced configs, CPU).

For every assigned arch: instantiate the SMOKE config, run one forward and
one full train step (loss + grads + AdamW), assert output shapes and
finiteness; run prefill + one decode step for the serving families.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_arch
from repro.configs.base import NodeConfig
from repro.data.tokens import synthetic_lm_batch
from repro.train import (TrainConfig, init_train_state, make_decode_step,
                         make_prefill_step, make_train_step)

B, S = 2, 16

# compile-heavy architectures (multi-layer units / very wide smoke configs):
# their smoke tests dominate suite wall time, so the CI fast lane skips them
# (-m "not slow"); the full-suite job still runs every architecture.
HEAVY_ARCHS = {"jamba-v0.1-52b", "xlstm-1.3b", "deepseek-v2-lite-16b",
               "mixtral-8x7b"}
ARCH_PARAMS = [pytest.param(a, marks=pytest.mark.slow)
               if a in HEAVY_ARCHS else a for a in ARCH_IDS]


def _batch(arch):
    b = synthetic_lm_batch(0, B, S + 1, arch.vocab)
    batch = {"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"])}
    if arch.encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, S, arch.d_frontend))
    if arch.frontend == "patch":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, 4, arch.d_frontend))
    return batch


def _finite(tree):
    return all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(tree)
               if jnp.issubdtype(l.dtype, jnp.floating))


@pytest.mark.parametrize("arch_id", ARCH_PARAMS)
def test_train_step_smoke(arch_id):
    arch = get_smoke_arch(arch_id)
    tcfg = TrainConfig(lr=1e-3)
    state = init_train_state(jax.random.PRNGKey(0), arch, tcfg)
    step = jax.jit(make_train_step(arch, tcfg))
    batch = _batch(arch)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch_id
    assert _finite(state["params"]), arch_id
    # loss decreases over a few steps (sanity that gradients are useful)
    first = float(metrics["loss"])
    for _ in range(3):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < first, arch_id


@pytest.mark.parametrize("arch_id", ARCH_PARAMS)
def test_prefill_decode_smoke(arch_id):
    arch = get_smoke_arch(arch_id)
    tcfg = TrainConfig()
    state = init_train_state(jax.random.PRNGKey(0), arch, tcfg)
    params = state["params"]
    max_len = S + 8
    prefill = jax.jit(make_prefill_step(arch, B, max_len))
    decode = jax.jit(make_decode_step(arch))
    batch = _batch(arch)
    logits, caches = prefill(params, batch)
    assert logits.shape == (B, 1, arch.vocab), arch_id
    assert _finite(logits), arch_id
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    # decode position: SSM/xLSTM states are positionless; attention caches
    # append at S (or S + n_patches for the VLM prefix).
    pos = jnp.int32(S + (4 if arch.frontend == "patch" else 0))
    logits2, caches = decode(params, caches, tok, pos)
    assert logits2.shape == (B, 1, arch.vocab), arch_id
    assert _finite(logits2), arch_id


@pytest.mark.parametrize("arch_id", [
    "qwen3-0.6b",
    pytest.param("mixtral-8x7b", marks=pytest.mark.slow),
    pytest.param("xlstm-1.3b", marks=pytest.mark.slow),
    pytest.param("deepseek-v2-lite-16b", marks=pytest.mark.slow)])
def test_node_mode_smoke(arch_id):
    """The paper's technique on a reduced config of each family kind."""
    arch = get_smoke_arch(arch_id).with_(
        node=NodeConfig(mode="node", method="euler",
                        grad_mode="symplectic"))
    tcfg = TrainConfig(lr=1e-3)
    state = init_train_state(jax.random.PRNGKey(0), arch, tcfg)
    step = jax.jit(make_train_step(arch, tcfg))
    state, metrics = step(state, _batch(arch))
    assert np.isfinite(float(metrics["loss"])), arch_id
    assert _finite(state["params"]), arch_id
