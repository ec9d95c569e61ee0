"""Trace-size regression tests for the scan-segmented SaveAt drivers.

The segmented drivers (``_segmented`` in core/api.py, the symplectic
driver's segmented forward and replay, ``rk_solve_segments``) run their
per-observation segments inside ``lax.scan``, so the traced program is ONE
segment body regardless of how many observation times the caller passes.
These tests pin that property down as a jaxpr *equation count* invariant:
growing ``len(ts)`` 8x may not grow the jaxpr by more than 10% — for the
forward value AND the full reverse-mode gradient of every gradient mode,
and for the component dimension of the CNF stack.

A regression back to Python-loop segmentation makes these counts linear in
``len(ts)`` (hundreds of percent, not <10%), so the bound is loose to
tracer-noise but tight to the failure mode.
"""
import jax
import jax.numpy as jnp
import pytest

jax.config.update("jax_enable_x64", True)

from repro.analysis import count_eqns
from repro.core import AdaptiveConfig, GRAD_MODES, odeint

# Deliberately exercises the deprecated odeint shim (shim regression suite).
pytestmark = pytest.mark.filterwarnings(
    "ignore:odeint-style entry point:DeprecationWarning")

ADAPTIVE_MODES = ["symplectic", "backprop", "adjoint"]


def mlp_field(x, t, params):
    h = jnp.tanh(params["w1"] @ x + params["b1"] + t)
    return params["w2"] @ h + params["b2"]


def make_params(key, dim=4, hidden=6):
    ks = jax.random.split(key, 4)
    return {
        "w1": jax.random.normal(ks[0], (hidden, dim)) * 0.5,
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(ks[2], (dim, hidden)) * 0.5,
        "b2": jnp.zeros((dim,)),
    }


PARAMS = make_params(jax.random.PRNGKey(0))
X0 = jnp.ones(4)


def _ts(n):
    return jnp.linspace(0.1, 1.0, n)


def _assert_flat(counts, context):
    c_small, c_big = counts
    assert c_big <= 1.1 * c_small, (
        f"{context}: jaxpr equation count grew {c_small} -> {c_big} "
        f"({c_big / c_small:.2f}x) when len(ts) grew 8x — the segmented "
        "driver is tracing per-observation again")


@pytest.mark.parametrize("mode", list(GRAD_MODES))
def test_fixed_grid_saveat_value_trace_flat(mode):
    def value(x0, params, n):
        return odeint(mlp_field, x0, params, ts=_ts(n), method="dopri5",
                      grad_mode=mode, n_steps=3)

    counts = [count_eqns(jax.make_jaxpr(
        lambda x, p: value(x, p, n))(X0, PARAMS).jaxpr) for n in (4, 32)]
    _assert_flat(counts, f"value[{mode}]")


@pytest.mark.parametrize("mode", list(GRAD_MODES))
def test_fixed_grid_saveat_grad_trace_flat(mode):
    def loss(x0, params, n):
        ys = odeint(mlp_field, x0, params, ts=_ts(n), method="dopri5",
                    grad_mode=mode, n_steps=3)
        return jnp.sum(jnp.sin(ys) ** 2)

    counts = [count_eqns(jax.make_jaxpr(jax.grad(
        lambda x, p: loss(x, p, n), argnums=(0, 1)))(X0, PARAMS).jaxpr)
        for n in (4, 32)]
    _assert_flat(counts, f"grad[{mode}]")


@pytest.mark.parametrize("mode", ADAPTIVE_MODES)
def test_adaptive_saveat_value_trace_flat(mode):
    cfg = AdaptiveConfig(max_steps=16, initial_step=0.05)

    def value(x0, params, n):
        return odeint(mlp_field, x0, params, ts=_ts(n), method="dopri5",
                      grad_mode=mode, adaptive=cfg)

    counts = [count_eqns(jax.make_jaxpr(
        lambda x, p: value(x, p, n))(X0, PARAMS).jaxpr) for n in (4, 32)]
    _assert_flat(counts, f"adaptive value[{mode}]")


@pytest.mark.parametrize("mode", ["symplectic", "adjoint"])
def test_adaptive_saveat_grad_trace_flat(mode):
    cfg = AdaptiveConfig(max_steps=16, initial_step=0.05)

    def loss(x0, params, n):
        ys = odeint(mlp_field, x0, params, ts=_ts(n), method="dopri5",
                    grad_mode=mode, adaptive=cfg)
        return jnp.sum(jnp.sin(ys) ** 2)

    counts = [count_eqns(jax.make_jaxpr(jax.grad(
        lambda x, p: loss(x, p, n), argnums=(0, 1)))(X0, PARAMS).jaxpr)
        for n in (4, 32)]
    _assert_flat(counts, f"adaptive grad[{mode}]")


def test_cnf_flow_path_trace_flat_in_components_and_ts():
    """The CNF stack scans over STACKED component params, and each
    component solve scans over observation segments: the flow-path trace is
    O(1) in both n_components and len(ts)."""
    from repro.models.cnf import CNFConfig, cnf_flow_path, init_cnf

    def build(m, n):
        cfg = CNFConfig(dim=3, hidden=(8,), n_components=m, n_steps=3,
                        trace="exact", method="bosh3")
        params = init_cnf(jax.random.PRNGKey(0), cfg)
        u = jnp.ones((2, 3))
        eps = jnp.ones((2, 3))
        return count_eqns(jax.make_jaxpr(
            lambda p: cnf_flow_path(p, u, eps, cfg, _ts(n)))(params).jaxpr)

    c_small = build(1, 4)
    c_big = build(8, 32)
    assert c_big <= 1.1 * c_small, (c_small, c_big)


def test_rollout_trace_flat_in_horizon():
    """physics.rollout horizons ride the scanned SaveAt path."""
    from repro.models.physics import PhysicsConfig, init_energy_net, rollout

    cfg = PhysicsConfig(grid=16, channels=4, hidden=8, method="bosh3",
                        n_steps=2)
    params = init_energy_net(jax.random.PRNGKey(0), cfg)
    u0 = jnp.ones((2, 16))

    def count(horizon):
        return count_eqns(jax.make_jaxpr(
            lambda p: rollout(p, u0, cfg, horizon))(params).jaxpr)

    assert count(64) <= 1.1 * count(4), (count(4), count(64))


def test_64_observation_rollout_compiles_and_grads():
    """A 64-observation symplectic SaveAt solve COMPILES (not just traces)
    within the CI budget and its gradient against a decimated reference is
    exact: the long-horizon capability the scan segmentation buys.  The
    unrolled drivers could not compile this in CI
    (benchmarks/bench_saveat_compile.py quantifies the wall-clock gap)."""
    ts64 = jnp.linspace(1.0 / 64, 1.0, 64)

    def loss(x0, params):
        ys = odeint(mlp_field, x0, params, ts=ts64, method="dopri5",
                    grad_mode="symplectic", n_steps=2)
        return jnp.sum(jnp.sin(ys) ** 2), ys

    (val, ys), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(X0, PARAMS)
    assert ys.shape == (64, 4)
    assert bool(jnp.all(jnp.isfinite(ys)))
    for g in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))
    # the observation at ts64[31] must equal a direct solve to that time
    # with the same accumulated grid (32 segments x 2 steps = 64 steps)
    import numpy as np
    y_direct = odeint(mlp_field, X0, PARAMS, ts=ts64[:32], method="dopri5",
                      grad_mode="backprop", n_steps=2)
    np.testing.assert_allclose(np.asarray(ys[31]), np.asarray(y_direct[-1]),
                               rtol=1e-12, atol=1e-14)
