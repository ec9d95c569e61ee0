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
from repro.kernels.flash_attention import (_kv_block, _tile_class,
                                           flash_attention_pallas,
                                           flash_plan)
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_planned_blocks_match_ref(case, dtype):
    """The blocks ``flash_plan`` chooses when the caller names none."""
    B, H, Hkv, Sq, Sk, D, causal, window, q_offset = case
    keys = jax.random.split(jax.random.PRNGKey(43), 3)
    q = _rand(keys[0], (B, H, Sq, D), dtype)
    k = _rand(keys[1], (B, Hkv, Sk, D), dtype)
    v = _rand(keys[2], (B, Hkv, Sk, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


PLANNED_CASES = {
    # (B, H, Hkv, Sq, Sk, D, Dv, causal, window, q_offset, scale, blocks)
    # qwen3-0.6b's heads (group 2, D 128) at a short length: 512 blocks,
    # one interior tile, two on the diagonal, one dead
    "qwen3_heads": (1, 4, 2, 1024, 1024, 128, 128, True, None, 0, None,
                    None),
    # DeepSeek-V2's latent heads: qk 192, v 128
    "latent_heads": (1, 2, 2, 1024, 1024, 192, 128, True, None, 0, 0.11472,
                     None),
    # a length no block divides: the last kv tile holds pad keys
    "ragged": (1, 4, 2, 1000, 1000, 64, 64, True, None, 0, None, None),
    # the window's far edge inside 512 tiles, an interior tile behind the
    # diagonal
    "window_edge": (1, 4, 1, 2048, 2048, 64, 64, True, 1100, 0, None, None),
    # small tiles: interior tiles inside the window band, dead ones behind it
    "window_band": (1, 4, 2, 512, 512, 64, 64, True, 200, 0, None,
                    (64, 64)),
    # chunked prefill: an interior kv tile before the diagonal
    "q_offset": (1, 4, 2, 256, 1024, 64, 64, True, None, 768, None, None),
    "q_offset_ragged": (1, 4, 2, 200, 1000, 64, 64, True, None, 800, None,
                        None),
    "non_causal_ragged": (1, 2, 1, 300, 700, 64, 32, False, None, 0, None,
                          None),
}


@pytest.mark.parametrize("case", list(PLANNED_CASES))
def test_flash_attention_edge_and_interior_tiles_match_ref(case):
    (B, H, Hkv, Sq, Sk, D, Dv, causal, window, q_offset, scale,
     blocks) = PLANNED_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    q = _rand(keys[0], (B, H, Sq, D), jnp.float32)
    k = _rand(keys[1], (B, Hkv, Sk, D), jnp.float32)
    v = _rand(keys[2], (B, Hkv, Sk, Dv), jnp.float32)
    kw = dict(causal=causal, window=window, q_offset=q_offset, scale=scale)
    bq, bk = blocks or (None, None)
    plan = flash_plan(B, H, Hkv, Sq, Sk, D, Dv, causal=causal, window=window,
                      q_offset=q_offset, block_q=bq, block_k=bk)
    assert 0 < plan.edge_tiles < plan.live_tiles, plan   # both kinds run
    got = flash_attention_pallas(q, k, v, block_q=bq, block_k=bk,
                                 interpret=True, **kw)
    want = ref.attention_ref(q, k, v, **kw)
    assert got.shape == want.shape == (B, H, Sq, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- the tile schedule -------------------------------------------------------

PLAN_CASES = {
    # (B, H, Hkv, Sq, Sk, D, Dv, causal, window, q_offset, blocks)
    "causal": (2, 4, 2, 1024, 1024, 128, 128, True, None, 0, None),
    "causal_small_blocks": (1, 2, 1, 512, 512, 64, 64, True, None, 0,
                            (128, 64)),
    "ragged": (1, 4, 4, 1000, 1000, 64, 64, True, None, 0, None),
    "window": (1, 8, 2, 2048, 2048, 128, 128, True, 700, 0, None),
    "window_small_blocks": (1, 2, 2, 640, 640, 64, 64, True, 150, 0,
                            (128, 128)),
    "decode": (4, 16, 8, 1, 4096, 128, 128, True, None, 4095, None),
    "decode_window": (1, 16, 8, 1, 300, 64, 64, True, 128, 299, None),
    "chunked_prefill": (2, 16, 8, 256, 2048, 128, 128, True, None, 1792,
                        None),
    "non_causal": (4, 8, 8, 600, 700, 64, 64, False, None, 0, None),
    "latent": (4, 16, 16, 4096, 4096, 192, 128, True, None, 0, None),
}


def _brute_tiles(Sq, Sk, causal, window, q_offset, bq, bk):
    """(live, inner) per tile from the mask itself: a tile is live if the
    mask keeps a pair of its real queries, inner if it keeps every pair of
    them (pad keys count as masked)."""
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    qpos = q_offset + np.arange(Sq)[:, None]
    kpos = np.arange(nk * bk)[None, :]
    keep = kpos < Sk
    if causal:
        keep = keep & (kpos <= qpos)
    if window is not None:
        keep = keep & (kpos > qpos - window)
    keep = np.broadcast_to(keep, (Sq, nk * bk))
    live = np.zeros((nq, nk), bool)
    inner = np.zeros((nq, nk), bool)
    for iq in range(nq):
        for ik in range(nk):
            t = keep[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            live[iq, ik], inner[iq, ik] = t.any(), t.all()
    return live, inner


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_flash_plan_counts_match_the_mask(case):
    B, H, Hkv, Sq, Sk, D, Dv, causal, window, q_offset, blocks = \
        PLAN_CASES[case]
    bq, bk = blocks or (None, None)
    plan = flash_plan(B, H, Hkv, Sq, Sk, D, Dv, causal=causal, window=window,
                      q_offset=q_offset, block_q=bq, block_k=bk)
    bq, bk = plan.block_q, plan.block_k
    live, inner = _brute_tiles(Sq, Sk, causal, window, q_offset, bq, bk)
    nq, nk = live.shape
    assert plan.grid == (B, Hkv, nq, nk)
    assert plan.grid_steps == B * Hkv * nq * nk
    assert plan.live_tiles == B * Hkv * int(live.sum())
    assert plan.edge_tiles == B * Hkv * int((live & ~inner).sum())
    # the kernel's own tile test agrees tile by tile
    q_lo = np.arange(nq)[:, None] * bq + q_offset
    k_lo = np.arange(nk)[None, :] * bk
    k_live, k_inner = _tile_class(q_lo, k_lo, bq=bq, bk=bk,
                                  q_end=q_offset + Sq - 1, kv_len=Sk,
                                  causal=causal, window=window)
    np.testing.assert_array_equal(np.broadcast_to(k_live, live.shape), live)
    np.testing.assert_array_equal(np.broadcast_to(k_inner, live.shape),
                                  inner)


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_flash_plan_fetches_no_dead_tile(case):
    """A live tile fetches its own kv block; a dead one names the block its
    q block already holds: the last live one past the causal frontier, the
    first live one behind the window.  So K/V copies are counted from the
    live tiles alone."""
    B, H, Hkv, Sq, Sk, D, Dv, causal, window, q_offset, blocks = \
        PLAN_CASES[case]
    bq, bk = blocks or (None, None)
    plan = flash_plan(B, H, Hkv, Sq, Sk, D, Dv, causal=causal, window=window,
                      q_offset=q_offset, block_q=bq, block_k=bk)
    bq, bk = plan.block_q, plan.block_k
    live, _ = _brute_tiles(Sq, Sk, causal, window, q_offset, bq, bk)
    nq, nk = live.shape
    fetched = []
    for iq in range(nq):
        cols = np.flatnonzero(live[iq])
        for ik in range(nk):
            got = int(_kv_block(iq, ik, bq=bq, bk=bk, nk=nk,
                                q_offset=q_offset, q_end=q_offset + Sq - 1,
                                causal=causal, window=window, xp=np))
            if live[iq, ik]:
                assert got == ik, (iq, ik, got)
            elif ik > cols[-1]:
                assert got == cols[-1], (iq, ik, got)
            else:
                assert got == cols[0], (iq, ik, got)
            fetched.append(got)
    copies = 1 + sum(a != b for a, b in zip(fetched, fetched[1:]))
    assert copies <= live.sum()
    assert plan.kv_bytes == B * Hkv * copies * bk * (D + Dv) * 4


def test_flash_plan_cell_shapes():
    """The LM cells' calls: qwen3-0.6b at 8 x 1024 and DeepSeek-V2-Lite's
    latent attention at 4 x 4096, against 8,192 and 65,536 grid steps of
    128 x 128 tiles one q head at a time."""
    qwen3 = flash_plan(8, 16, 8, 1024, 1024, 128, 128)
    assert (qwen3.block_q, qwen3.block_k) == (512, 512)
    assert qwen3.grid_steps == 256 <= 8192 // 8
    assert qwen3.kv_bytes == 64 * 2**20             # 1.07 GB at 128 x 128
    assert flash_plan(4, 16, 16, 4096, 4096, 192, 128).grid_steps == 4096


@pytest.mark.parametrize("Sk", [1, 100, 300, 4096, 5000])
def test_flash_plan_decode_no_worse_than_128_blocks(Sk):
    plan = flash_plan(2, 16, 8, 1, Sk, 128, 128, q_offset=Sk - 1)
    assert plan.block_q == 1
    assert plan.grid_steps <= 2 * 16 * -(-Sk // 128)
    pad_at_128 = Sk if Sk <= 128 else -(-Sk // 128) * 128
    assert plan.grid[3] * plan.block_k <= pad_at_128


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
