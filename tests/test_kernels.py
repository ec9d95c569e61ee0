"""Per-kernel interpret-mode validation against the pure-jnp oracles.

Shape/dtype sweeps (parametrized + hypothesis) with assert_allclose per the
deliverable spec.  All Pallas execution uses interpret=True (CPU container).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal container: run fixed examples instead
    from hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.butcher_combine import butcher_combine_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rms_norm_pallas

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


# --- butcher_combine ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,s", [((64,), 4), ((33, 7), 7),
                                     ((4, 128, 128), 13), ((1,), 1),
                                     ((1024,), 6)])
def test_butcher_combine_matches_ref(shape, s, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = _rand(keys[0], shape, dtype)
    ks = _rand(keys[1], (s,) + shape, dtype)
    coefs = jax.random.normal(keys[2], (s,), dtype=jnp.float32)
    h = jnp.float32(0.125)
    got = butcher_combine_pallas(x, ks, coefs, h, interpret=True)
    want = ref.butcher_combine_ref(x, ks, coefs, h)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 700), s=st.integers(1, 13),
       block_rows=st.sampled_from([8, 64, 256]))
def test_butcher_combine_property(n, s, block_rows):
    keys = jax.random.split(jax.random.PRNGKey(n * 31 + s), 3)
    x = _rand(keys[0], (n,), jnp.float32)
    ks = _rand(keys[1], (s, n), jnp.float32)
    coefs = jax.random.normal(keys[2], (s,))
    h = jnp.float32(0.01)
    got = butcher_combine_pallas(x, ks, coefs, h, block_rows=block_rows,
                                 interpret=True)
    want = ref.butcher_combine_ref(x, ks, coefs, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --- rms_norm ----------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 256), (3, 5, 128), (1, 1024),
                                   (130, 384)])
@pytest.mark.parametrize("with_residual", [False, True])
def test_rms_norm_matches_ref(shape, dtype, with_residual):
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = _rand(keys[0], shape, dtype)
    w = _rand(keys[1], (shape[-1],), jnp.float32)
    res = _rand(keys[2], shape, dtype) if with_residual else None
    got = rms_norm_pallas(x, w, res, interpret=True)
    want = ref.rms_norm_ref(x, w, res)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@settings(max_examples=15, deadline=None)
@given(rows=st.integers(1, 300), d=st.sampled_from([128, 256, 512]),
       block_rows=st.sampled_from([8, 128]))
def test_rms_norm_property(rows, d, block_rows):
    keys = jax.random.split(jax.random.PRNGKey(rows * 7 + d), 2)
    x = _rand(keys[0], (rows, d), jnp.float32)
    w = _rand(keys[1], (d,), jnp.float32)
    got = rms_norm_pallas(x, w, block_rows=block_rows, interpret=True)
    want = ref.rms_norm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- flash attention ---------------------------------------------------------

ATTN_CASES = [
    # (B, H, Hkv, Sq, Sk, D, causal, window, q_offset)
    (1, 4, 4, 128, 128, 64, True, None, 0),     # MHA causal
    (2, 8, 2, 256, 256, 64, True, None, 0),     # GQA causal
    (1, 4, 1, 128, 128, 128, True, 64, 0),      # MQA + sliding window
    (1, 4, 2, 100, 100, 64, True, None, 0),     # ragged (padding path)
    (2, 8, 4, 1, 512, 64, True, None, 511),     # decode: 1 query vs cache
    (1, 4, 4, 64, 256, 64, True, None, 192),    # chunked prefill offset
    (1, 4, 4, 128, 128, 64, False, None, 0),    # non-causal (encoder)
    (1, 16, 8, 1, 300, 64, True, 128, 299),     # decode + SWA, ragged cache
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_ref(case, dtype):
    B, H, Hkv, Sq, Sk, D, causal, window, q_offset = case
    keys = jax.random.split(jax.random.PRNGKey(42), 3)
    q = _rand(keys[0], (B, H, Sq, D), dtype)
    k = _rand(keys[1], (B, Hkv, Sk, D), dtype)
    v = _rand(keys[2], (B, Hkv, Sk, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, block_q=64, block_k=64,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block_q,block_k", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shape_invariance(block_q, block_k):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(keys[0], (1, 4, 200, 64), jnp.float32)
    k = _rand(keys[1], (1, 2, 200, 64), jnp.float32)
    v = _rand(keys[2], (1, 2, 200, 64), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=block_q,
                                 block_k=block_k, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_swa_equals_full_when_window_covers():
    """window >= Sk must equal unwindowed attention."""
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(keys[0], (1, 2, 64, 32), jnp.float32)
    k = _rand(keys[1], (1, 2, 64, 32), jnp.float32)
    v = _rand(keys[2], (1, 2, 64, 32), jnp.float32)
    a = flash_attention_pallas(q, k, v, causal=True, window=4096,
                               block_q=32, block_k=32, interpret=True)
    b = flash_attention_pallas(q, k, v, causal=True, window=None,
                               block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Sq,Sk,q_offset", [(128, 128, 0), (100, 100, 0),
                                            (1, 200, 199)])
def test_flash_attention_v_head_dim_differs(Sq, Sk, q_offset, dtype):
    """Latent attention's head dims: qk 192, v 128 (DeepSeek-V2)."""
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q = _rand(keys[0], (1, 2, Sq, 192), dtype)
    k = _rand(keys[1], (1, 2, Sk, 192), dtype)
    v = _rand(keys[2], (1, 2, Sk, 128), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, q_offset=q_offset,
                                 scale=0.11472, block_q=64, block_k=64,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=q_offset,
                             scale=0.11472)
    assert got.shape == want.shape == (1, 2, Sq, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("Dv", [16, 48])
def test_attention_blocked_ref_v_head_dim_differs(Dv):
    keys = jax.random.split(jax.random.PRNGKey(14), 3)
    q = _rand(keys[0], (2, 4, 64, 24), jnp.float32)
    k = _rand(keys[1], (2, 2, 64, 24), jnp.float32)
    v = _rand(keys[2], (2, 2, 64, Dv), jnp.float32)
    got = ref.attention_blocked_ref(q, k, v, causal=True, block_q=16)
    want = ref.attention_ref(q, k, v, causal=True)
    assert got.shape == want.shape == (2, 4, 64, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- reverse mode through the Pallas branches of ops ------------------------
#
# ops.rms_norm / ops.attention wrap the kernels in custom VJPs whose backward
# is the VJP of the jnp oracle.  Off the TPU, use_pallas=True runs the kernel
# forward in interpret mode, so these gradients go through the same
# custom_vjp the chip runs.

def _grads(f, args, cot_key):
    out = f(*args)
    cot = _rand(cot_key, out.shape, jnp.float32)
    return jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                    argnums=tuple(range(len(args))))(*args)


def _assert_trees_close(got, want, **tol):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **tol)


@pytest.mark.parametrize("with_residual", [False, True])
def test_rms_norm_pallas_grad_matches_ref(with_residual):
    from repro.kernels import ops
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = _rand(keys[0], (3, 40, 128), jnp.float32)
    w = _rand(keys[1], (128,), jnp.float32)
    args = (x, w) + ((_rand(keys[2], x.shape, jnp.float32),)
                     if with_residual else ())
    got = _grads(lambda *a: ops.rms_norm(*a, eps=1e-5, use_pallas=True),
                 args, keys[3])
    want = _grads(lambda *a: ref.rms_norm_ref(*a, eps=1e-5), args, keys[3])
    assert len(got) == len(args)
    _assert_trees_close(got, want, rtol=1e-5, atol=1e-5)


GRAD_ATTN_CASES = {
    # (B, H, Hkv, Sq, Sk, D, causal, window, q_offset)
    "causal": (1, 2, 2, 64, 64, 32, True, None, 0),
    "gqa": (2, 4, 2, 64, 64, 32, True, None, 0),
    "sliding_window": (1, 4, 1, 96, 96, 32, True, 24, 0),
    "q_offset": (1, 4, 2, 32, 96, 32, True, None, 64),
    "non_causal": (1, 2, 1, 48, 80, 32, False, None, 0),
}


@pytest.mark.parametrize("case", list(GRAD_ATTN_CASES))
def test_attention_pallas_grad_matches_ref(case):
    from repro.kernels import ops
    B, H, Hkv, Sq, Sk, D, causal, window, q_offset = GRAD_ATTN_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _rand(keys[0], (B, H, Sq, D), jnp.float32)
    k = _rand(keys[1], (B, Hkv, Sk, D), jnp.float32)
    v = _rand(keys[2], (B, Hkv, Sk, D), jnp.float32)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    got = _grads(lambda *a: ops.attention(*a, use_pallas=True, **kw),
                 (q, k, v), keys[3])
    want = _grads(lambda *a: ref.attention_ref(*a, **kw), (q, k, v),
                  keys[3])
    _assert_trees_close(got, want, rtol=1e-5, atol=1e-5)


def test_attention_pallas_vjp_saves_only_inputs():
    """The forward of the kernel's VJP keeps (q, k, v), never the scores."""
    from repro.kernels import ops
    q = jnp.ones((1, 2, 64, 32))
    k = v = jnp.ones((1, 1, 64, 32))
    _, vjp = jax.vjp(lambda *a: ops.attention(*a, use_pallas=True), q, k, v)
    sizes = sorted(leaf.size for leaf in jax.tree_util.tree_leaves(vjp))
    assert max(sizes) <= q.size, sizes      # no (Sq, Sk)-sized residual


def test_node_lm_grad_pallas_matches_jnp():
    """A NODE-mode smoke LM trained with symplectic gradients: the kernel
    path (Pallas norm, attention and stage combine, interpret mode here)
    gives the gradient of the jnp path."""
    from repro.configs import get_smoke_arch
    from repro.configs.base import NodeConfig
    from repro.data.tokens import synthetic_lm_batch
    from repro.train import TrainConfig, init_train_state, make_grad_fn

    base = get_smoke_arch("qwen3-0.6b")
    tcfg = TrainConfig()
    params = init_train_state(jax.random.PRNGKey(0), base, tcfg).params
    batch = {k: jnp.asarray(a) for k, a in
             synthetic_lm_batch(0, 2, 17, base.vocab).items()}

    def grads(use_pallas, backend):
        arch = base.with_(use_pallas=use_pallas, node=NodeConfig(
            mode="node", method="euler", grad_mode="symplectic",
            combine_backend=backend))
        g, loss, _ = make_grad_fn(arch, tcfg)(params, batch)
        return g, loss

    g_k, l_k = grads(True, "pallas")
    g_j, l_j = grads(False, "jnp")
    np.testing.assert_allclose(float(l_k), float(l_j), rtol=1e-5)
    _assert_trees_close(g_k, g_j, rtol=1e-4, atol=1e-6)
