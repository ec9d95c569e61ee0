"""DeepSeek-V2's block against plain references on seeded weights (CPU,
smoke sizes): latent attention with YaRN, the expert layer that holds one
chip's share of the routed experts, the sequence-level balance loss, and
the NODE depth solve with the dense prefix layer and the balance loss
inside it."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_arch
from repro.configs.base import NodeConfig
from repro.data.tokens import synthetic_lm_batch
from repro.models import lm
from repro.nn.attention import AttnConfig, init_mla, mla_attention
from repro.nn.moe import MoEConfig, held_counts, init_moe, moe_ffn, moe_route
from repro.nn.rope import (YaRN, rope_freqs, yarn_amplitude,
                           yarn_correction_range, yarn_softmax_scale)
from repro.train import TrainConfig, init_train_state, make_grad_fn

V2_LITE_YARN = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _close(got, want, rel):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        assert g.shape == w.shape
        scale = max(float(jnp.max(jnp.abs(w))), 1e-30)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=rel * scale)


# --- YaRN and latent attention ----------------------------------------------

def test_yarn_numbers_of_v2_lite():
    """Rope dim 64, base 1e4, factor 40: ramp between dims 10 and 23;
    softmax scale 192^-0.5 * mscale(40, 0.707)^2; cos and sin unscaled."""
    assert yarn_correction_range(64, 1e4, V2_LITE_YARN) == (10, 23)
    assert math.isclose(0.1 * 0.707 * math.log(40) + 1, 1.2608, abs_tol=1e-4)
    assert math.isclose(yarn_softmax_scale(192, V2_LITE_YARN), 0.11472,
                        abs_tol=1e-5)
    assert yarn_amplitude(V2_LITE_YARN) == 1.0
    plain = np.asarray(rope_freqs(64, 1e4, 64))
    yarn = np.asarray(rope_freqs(64, 1e4, 64, yarn=V2_LITE_YARN))
    np.testing.assert_allclose(yarn[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(yarn[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        yarn[11:23], plain[11:23] / 40 * ramp + plain[11:23] * (1 - ramp),
        rtol=1e-6)


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mla_written_out(hf, x, H, dn, dr, dv, lora, theta, y):
    """DeepSeek-V2's attention as its modelling code writes it: the
    published projections, de-interleaved rope rotating halves, YaRN."""
    B, S, _ = x.shape
    q = (x @ hf["q_proj"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = x @ hf["kv_a_proj_with_mqa"]
    c, k_pe = kv_a[..., :lora], kv_a[..., lora:]
    kv = (_rms(c, hf["kv_a_layernorm"]) @ hf["kv_b_proj"]).reshape(
        B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    low, high = yarn_correction_range(dr, theta, y)
    base = 1.0 / theta ** (np.arange(0, dr, 2) / dr)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = base / y.factor * ramp + base * (1 - ramp)
    emb = np.concatenate([np.outer(np.arange(S), inv)] * 2, -1)
    cos, sin = jnp.asarray(np.cos(emb)), jnp.asarray(np.sin(emb))

    def rope(t):   # (B, S, h, dr): de-interleave, then rotate halves
        t = t.reshape(*t.shape[:-1], dr // 2, 2).swapaxes(-1, -2).reshape(
            t.shape)
        rot = jnp.concatenate([-t[..., dr // 2:], t[..., :dr // 2]], -1)
        return t * cos[None, :, None] + rot * sin[None, :, None]

    q = jnp.concatenate([q_nope, rope(q_pe)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        rope(k_pe[:, :, None]), (B, S, H, dr))], -1)
    m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5 * m * m
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, H * dv) @ hf["o_proj"]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_mla_with_yarn_matches_written_out(use_pallas):
    H, dn, dr, dv, lora, d = 4, 16, 8, 12, 32, 48
    cfg = AttnConfig(d_model=d, n_heads=H, n_kv_heads=H, head_dim=dn,
                     mla=True, kv_lora=lora, rope_head_dim=dr,
                     nope_head_dim=dn, v_head_dim=dv, rope_theta=1e4,
                     rope_scaling=V2_LITE_YARN)
    p = init_mla(jax.random.PRNGKey(0), cfg)
    p["kv_norm"] = {"w": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), (lora,))}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, d))
    wkvb = jnp.concatenate([p["wuk"].reshape(lora, H, dn),
                            p["wuv"].reshape(lora, H, dv)], -1)
    hf = {"q_proj": p["wq"], "o_proj": p["wo"],
          "kv_a_proj_with_mqa": jnp.concatenate([p["wdkv"], p["wkr"]], -1),
          "kv_a_layernorm": p["kv_norm"]["w"],
          "kv_b_proj": wkvb.reshape(lora, H * (dn + dv))}
    with jax.default_matmul_precision("highest"):
        got, _ = mla_attention(p, x, cfg, use_pallas=use_pallas)
        want = _mla_written_out(hf, x, H, dn, dr, dv, lora, 1e4,
                                V2_LITE_YARN)
    _close(got, want, 2e-5)


# --- the held share of the routed experts ------------------------------------

E, K, D, F = 16, 4, 24, 20


def _moe_cfg(held=None, norm_topk_prob=False):
    return MoEConfig(d_model=D, d_ff=F, n_experts=E, top_k=K, n_shared=1,
                     shared_d_ff=28, norm_topk_prob=norm_topk_prob,
                     seq_aux=True, router_aux_weight=0.001,
                     held=held or (0, E))


def _share(p, first, n):
    return {**p, **{w: p[w][first:first + n] for w in ("wg", "wu", "wd")}}


def _dense_layer(p, x, cfg):
    """Every token through each of its top-k experts, one at a time."""
    scores = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, p["router"]), -1)
    gate, idx = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        h = jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        y = y + (h @ p["wd"][e]) * w[..., None]
    sp = p["shared"]
    return y + (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])) @ sp["wd"]


def _moe_setup():
    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    return cfg, p, x


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_held_shares_add_up_to_the_whole_layer(norm_topk_prob):
    """Eight chips of two experts each: their outputs, the shared expert
    counted once, sum to the uncut layer; each computes the same aux."""
    cfg, p, x = _moe_setup()
    cfg = _moe_cfg(norm_topk_prob=norm_topk_prob)
    n = E // 8
    with jax.default_matmul_precision("highest"):
        whole = _dense_layer(p, x, cfg)
        outs = [moe_ffn(_share(p, s * n, n), x, _moe_cfg(
            held=(s * n, n), norm_topk_prob=norm_topk_prob))
            for s in range(8)]
        sp = p["shared"]
        shared = (jax.nn.silu(x @ sp["wg"]) * (x @ sp["wu"])) @ sp["wd"]
        full, full_aux = moe_ffn(p, x, cfg)
    total = sum(y for y, _ in outs) - 7 * shared
    _close(total, whole, 1e-5)
    _close(full, whole, 1e-5)
    for _, aux in outs:
        assert float(aux) == float(full_aux)


@pytest.mark.parametrize("held", [(0, 2), (0, 8), (2, 4)])
def test_no_token_dropped_under_skewed_routing(held):
    """A router that sends every token to experts 0..k-1: the held share
    still computes every assignment it receives."""
    cfg, p, x = _moe_setup()
    p = {**p, "router": jnp.zeros_like(p["router"])}
    first, n = held
    share = _share(p, first, n)
    cfg_s = _moe_cfg(held=held)
    counts = np.asarray(held_counts(moe_route(share, x, cfg_s), cfg_s))
    want_counts = [x.shape[0] * x.shape[1] if first + i < K else 0
                   for i in range(n)]
    np.testing.assert_array_equal(counts, want_counts)
    with jax.default_matmul_precision("highest"):
        got, _ = moe_ffn(share, x, cfg_s)
        keep = jnp.zeros((E,), bool).at[first:first + n].set(True)
        masked = {**p, "wd": jnp.where(keep[:, None, None], p["wd"], 0.0)}
        want = _dense_layer(masked, x, cfg)
    _close(got, want, 1e-5)


def _ragged_dot_undefined_past_groups(real):
    """``jax.lax.ragged_dot`` whose output rows past ``sum(group_sizes)``,
    and the lhs cotangent's rows there, are NaN: what the operation leaves
    undefined (on the CPU it writes zeros there)."""
    def poison(y, sizes):
        rows = jnp.arange(y.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), y, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return poison(d_lhs, sizes), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


@pytest.mark.parametrize("held", [(0, 2), (2, 4), (5, 1)])
def test_rows_past_the_held_groups_reach_nothing(held, monkeypatch):
    """The static buffer holds T * min(k, n) rows and the held groups fill
    only some of them; whatever the grouped matmul leaves in the rest,
    forward or backward, reaches neither the output nor any gradient."""
    cfg, p, x = _moe_setup()
    first, n = held
    share, cfg_s = _share(p, first, n), _moe_cfg(held=held)
    r = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(p, x):
        y, aux = moe_ffn(p, x, cfg_s)
        return jnp.sum(y * r) + aux

    want = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _ragged_dot_undefined_past_groups(jax.lax.ragged_dot))
    got = jax.value_and_grad(loss, argnums=(0, 1))(share, x)
    for g in jax.tree_util.tree_leaves(got):
        assert bool(jnp.all(jnp.isfinite(g)))
    _close(got, want, 1e-6)


def test_sequence_aux_matches_formula():
    cfg, p, x = _moe_setup()
    route = moe_route(p, x, cfg)
    xs, w = np.asarray(x, np.float64), np.asarray(p["router"], np.float64)
    logits = xs @ w
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
    idx = np.argsort(-scores, -1, kind="stable")[..., :K]
    B, S = x.shape[:2]
    per_seq = []
    for b in range(B):
        counts = np.bincount(idx[b].ravel(), minlength=E)
        f = counts * E / (S * K)
        per_seq.append(np.sum(f * scores[b].mean(0)))
    want = 0.001 * np.mean(per_seq)
    np.testing.assert_array_equal(np.sort(np.asarray(route.idx), -1),
                                  np.sort(idx, -1))
    assert math.isclose(float(route.aux), want, rel_tol=1e-5)
    gate = np.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(np.sort(np.asarray(route.gate), -1),
                               np.sort(gate, -1), rtol=1e-5)


# --- the NODE depth solve: prefix and balance loss inside it -----------------

def _arch(mode, grad_mode="symplectic"):
    return get_smoke_arch("deepseek-v2-lite-16b").with_(
        n_layers=4, use_pallas=False,
        node=NodeConfig(mode=mode, method="euler", grad_mode=grad_mode,
                        combine_backend="jnp"))


def _params_batch():
    params = init_train_state(jax.random.PRNGKey(0), _arch("node"),
                              TrainConfig()).params
    batch = {k: jnp.asarray(a) for k, a in
             synthetic_lm_batch(0, 2, 17, _arch("node").vocab).items()}
    return params, batch


def test_node_euler_equals_discrete_stack():
    """Euler with n_steps = R is the discrete stack: the dense prefix runs
    before the solve and the units' balance losses add up to the same
    aux."""
    params, batch = _params_batch()
    outs = {}
    for mode in ("off", "node"):
        outs[mode] = jax.jit(lambda p, t, m=mode: lm.lm_forward(
            p, _arch(m), t, return_hidden=True))(params, batch["tokens"])
    assert float(outs["off"]["aux"]) > 0
    _close(outs["node"]["hidden"], outs["off"]["hidden"], 1e-5)
    assert math.isclose(float(outs["node"]["aux"]), float(outs["off"]["aux"]),
                        rel_tol=1e-5)
    # the prefix really runs: without it the hidden states differ
    no_prefix = {**params, "prefix_0": jax.tree_util.tree_map(
        jnp.zeros_like, params["prefix_0"])}
    skipped = lm.lm_forward(no_prefix, _arch("node"), batch["tokens"],
                            return_hidden=True)
    assert not np.allclose(skipped["hidden"], outs["node"]["hidden"])


def test_node_depth_states_are_the_unit_inputs():
    """``depth_input`` is what the scanned units take (the embedded tokens
    after the prefix layer), and the depth states from it end at the state
    ``lm_forward`` reaches."""
    params, batch = _params_batch()
    arch = _arch("node")
    x = lm.depth_input(params, arch, batch["tokens"])
    R = arch.n_repeats
    ys = lm.node_depth_states(params, arch, x, jnp.arange(1, R + 1) / R)
    assert ys.shape == (R,) + x.shape
    want = lm.lm_forward(params, arch, batch["tokens"], return_hidden=True)
    got, _ = lm._head_parts(params, arch, ys[-1])
    _close(got, want["hidden"], 1e-5)


def test_symplectic_gradient_equals_backprop():
    params, batch = _params_batch()
    tcfg = TrainConfig(loss_chunk=8)
    got = jax.jit(make_grad_fn(_arch("node"), tcfg))(params, batch)
    want = jax.jit(make_grad_fn(_arch("node", "backprop"), tcfg))(params,
                                                                  batch)
    assert math.isclose(float(got[1]), float(want[1]), rel_tol=1e-6)
    _close(got[0], want[0], 2e-5)
    discrete = jax.jit(make_grad_fn(_arch("off"), tcfg))(params, batch)
    _close(got[0], discrete[0], 2e-5)
