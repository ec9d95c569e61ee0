"""SaveAt trajectory observation + adaptive-controller bugfix tests.

Covers the acceptance criteria of the SaveAt subsystem:
  * odeint(..., ts=...) observes the solution at user times for ALL five
    gradient modes, on fixed and adaptive grids;
  * grad_mode="symplectic" matches jax.grad through the (segmented)
    discrete solver to rounding error for losses over >= 3 interior
    observation times, for dopri5 AND bosh3;
  * reverse-time (t1 < t0) and zero-length (t0 == t1) solves across all
    modes, including gradient exactness for the symplectic mode;
  * the adaptive controller fixes: unclamped-h carry across landing steps,
    dtype-aware termination threshold, and the ``succeeded`` flag with
    configurable on-failure policy.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core import (AdaptiveConfig, GRAD_MODES, get_tableau, odeint,
                        odeint_with_stats)
from repro.core.rk import (_time_resolution, rk_solve_adaptive,
                           rk_solve_segments, rk_step)

# Deliberately exercises the deprecated odeint shims (shim regression suite).
pytestmark = pytest.mark.filterwarnings(
    "ignore:odeint-style entry point:DeprecationWarning")

ALL_MODES = list(GRAD_MODES)
ADAPTIVE_MODES = ["symplectic", "backprop", "adjoint"]


def mlp_field(x, t, params):
    h = jnp.tanh(params["w1"] @ x + params["b1"] + t)
    return params["w2"] @ h + params["b2"]


def make_params(key, dim=4, hidden=6):
    ks = jax.random.split(key, 4)
    return {
        "w1": jax.random.normal(ks[0], (hidden, dim)) * 0.5,
        "b1": jax.random.normal(ks[1], (hidden,)) * 0.1,
        "w2": jax.random.normal(ks[2], (dim, hidden)) * 0.5,
        "b2": jax.random.normal(ks[3], (dim,)) * 0.1,
    }


def linear(x, t, p):
    return p["lam"] * x


LIN_P = {"lam": jnp.asarray(-0.7)}
TS3 = jnp.array([0.25, 0.5, 0.875])


# --- observation correctness -------------------------------------------------

@pytest.mark.parametrize("mode", ALL_MODES)
def test_saveat_fixed_matches_chained_segments(mode):
    """ts observation == chaining per-segment solves (the same discrete map),
    and tracks the closed-form solution."""
    x0 = jnp.asarray([1.0, 2.0, -0.5])
    ys = odeint(linear, x0, LIN_P, ts=TS3, method="dopri5", grad_mode=mode,
                n_steps=6)
    assert ys.shape == (3,) + x0.shape
    x, t_prev = x0, 0.0
    for i in range(3):
        x = odeint(linear, x, LIN_P, t0=t_prev, t1=TS3[i], method="dopri5",
                   grad_mode=mode, n_steps=6)
        np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(x),
                                   rtol=1e-12, atol=1e-14)
        t_prev = TS3[i]
    exact = x0 * jnp.exp(LIN_P["lam"] * TS3[:, None])
    np.testing.assert_allclose(np.asarray(ys), np.asarray(exact), rtol=1e-8)


@pytest.mark.parametrize("mode", ADAPTIVE_MODES)
def test_saveat_adaptive_observes(mode):
    x0 = jnp.asarray([1.0, 2.0, -0.5])
    cfg = AdaptiveConfig(rtol=1e-8, atol=1e-10, max_steps=64,
                         initial_step=0.05)
    ys = odeint(linear, x0, LIN_P, ts=TS3, method="dopri5", grad_mode=mode,
                adaptive=cfg)
    exact = x0 * jnp.exp(LIN_P["lam"] * TS3[:, None])
    np.testing.assert_allclose(np.asarray(ys), np.asarray(exact), rtol=1e-6)


def test_saveat_rejects_t1():
    with pytest.raises(ValueError, match="EITHER t1 or ts"):
        odeint(linear, jnp.ones(2), LIN_P, t1=1.0, ts=TS3)


def test_saveat_dense_requires_adaptive_backprop():
    with pytest.raises(ValueError, match="dense"):
        odeint(linear, jnp.ones(2), LIN_P, ts=TS3, ts_mode="dense",
               grad_mode="symplectic",
               adaptive=AdaptiveConfig())
    with pytest.raises(ValueError, match="dense"):
        odeint(linear, jnp.ones(2), LIN_P, ts=TS3, ts_mode="dense",
               grad_mode="backprop", n_steps=4)


# --- gradient exactness (the acceptance criterion) ---------------------------

@pytest.mark.parametrize("method", ["dopri5", "bosh3"])
def test_saveat_symplectic_gradient_exact_fixed(method):
    """Loss over 3 interior observation times: symplectic == jax.grad
    through the segmented solver, to rounding."""
    params = make_params(jax.random.PRNGKey(0))
    x0 = jax.random.normal(jax.random.PRNGKey(1), (4,))
    w = jnp.arange(1.0, 4.0)

    def loss(x0, params, mode):
        ys = odeint(mlp_field, x0, params, ts=TS3, method=method,
                    grad_mode=mode, n_steps=5)
        return jnp.sum(w[:, None] * jnp.sin(ys) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1))(x0, params, "backprop")
    g_sym = jax.grad(loss, argnums=(0, 1))(x0, params, "symplectic")
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sym)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)


@pytest.mark.slow   # unrolled replay reference over every accepted step
@pytest.mark.parametrize("method", ["dopri5", "bosh3"])
def test_saveat_symplectic_gradient_exact_adaptive(method):
    """Adaptive SaveAt: the symplectic backward pass reproduces the exact
    gradient of the realized segmented discrete map.  Reference: replay the
    recorded accepted steps of every segment as a differentiable unrolled
    solve with the observation loss applied at each segment boundary."""
    params = make_params(jax.random.PRNGKey(2))
    x0 = jax.random.normal(jax.random.PRNGKey(3), (4,))
    tab = get_tableau(method)
    cfg = AdaptiveConfig(rtol=1e-6, atol=1e-8, max_steps=64,
                         initial_step=0.1)
    w = jnp.arange(1.0, 4.0)

    obs, stacked = rk_solve_segments(mlp_field, tab, x0, 0.0, TS3, params,
                                     cfg)
    segs = []
    for i in range(TS3.shape[0]):
        s = jax.tree_util.tree_map(lambda l: l[i], stacked)
        assert bool(s.succeeded)
        n = int(s.n_accepted)
        assert 0 < n < cfg.max_steps
        segs.append((np.asarray(s.ts)[:n], np.asarray(s.hs)[:n]))

    def loss_replay(x0, params):
        x, tot = x0, 0.0
        for i, (tseq, hseq) in enumerate(segs):
            for t, h in zip(tseq, hseq):
                x, _ = rk_step(mlp_field, tab, x, jnp.asarray(t),
                               jnp.asarray(h), params)
            tot = tot + jnp.sum(w[i] * jnp.sin(x) ** 2)
        return tot

    def loss_sym(x0, params):
        ys = odeint(mlp_field, x0, params, ts=TS3, method=method,
                    grad_mode="symplectic", adaptive=cfg)
        return jnp.sum(w[:, None] * jnp.sin(ys) ** 2)

    # the symplectic SaveAt primal must equal the replay states
    ys = odeint(mlp_field, x0, params, ts=TS3, method=method,
                grad_mode="symplectic", adaptive=cfg)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(obs), rtol=1e-12)

    g_ref = jax.grad(loss_replay, argnums=(0, 1))(x0, params)
    g_sym = jax.grad(loss_sym, argnums=(0, 1))(x0, params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sym)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-9, atol=1e-11)


# --- dense output ------------------------------------------------------------

def test_dense_output_accuracy():
    """Hermite dense output tracks the true solution without the observation
    times entering the step sequence."""
    x0 = jnp.asarray([1.0, -2.0])
    cfg = AdaptiveConfig(rtol=1e-8, atol=1e-10, max_steps=256,
                         initial_step=0.02)
    taus = jnp.array([0.1, 0.37, 0.52, 0.81, 1.0])
    ys, stats = odeint_with_stats(linear, x0, LIN_P, ts=taus,
                                  method="dopri5", adaptive=cfg)
    assert bool(stats["succeeded"])
    exact = x0 * jnp.exp(LIN_P["lam"] * taus[:, None])
    np.testing.assert_allclose(np.asarray(ys), np.asarray(exact), rtol=1e-6)
    # same step sequence as the unobserved solve: the controller never saw ts
    _, stats0 = odeint_with_stats(linear, x0, LIN_P, t1=1.0,
                                  method="dopri5", adaptive=cfg)
    assert int(stats["n_steps"]) == int(stats0["n_steps"])
    assert int(stats["n_fevals"]) == int(stats0["n_fevals"]) + 2 * 5

    # differentiable dense path (grad_mode="backprop", ts_mode="dense")
    ys2 = odeint(linear, x0, LIN_P, ts=taus, method="dopri5",
                 grad_mode="backprop", adaptive=cfg, ts_mode="dense")
    np.testing.assert_allclose(np.asarray(ys2), np.asarray(ys), rtol=1e-12)


def test_dense_output_endpoints_exact():
    """At accepted-step endpoints the interpolant is exact (theta in {0,1})."""
    x0 = jnp.asarray([0.3, 1.7])
    cfg = AdaptiveConfig(rtol=1e-6, atol=1e-8, max_steps=64,
                         initial_step=0.1)
    tab = get_tableau("dopri5")
    sol = rk_solve_adaptive(linear, tab, x0, 0.0, 1.0, LIN_P, cfg)
    n = int(sol.n_accepted)
    from repro.core import hermite_observe
    taus = jnp.asarray(np.asarray(sol.ts)[1:n])   # interior step starts
    ys = hermite_observe(linear, tab, sol, LIN_P, taus)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(sol.xs)[1:n],
                               rtol=1e-12, atol=1e-14)


# --- reverse time and zero-length intervals ----------------------------------

@pytest.mark.parametrize("mode", ALL_MODES)
def test_reverse_time_fixed(mode):
    x0 = jnp.asarray([1.0, 0.5])
    y = odeint(linear, x0, LIN_P, t0=1.0, t1=0.0, method="dopri5",
               grad_mode=mode, n_steps=8)
    exact = x0 * jnp.exp(LIN_P["lam"] * (0.0 - 1.0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact), rtol=1e-8)


@pytest.mark.parametrize("method", ["dopri5", "bosh3"])
def test_reverse_time_symplectic_gradient_exact(method):
    params = make_params(jax.random.PRNGKey(4))
    x0 = jax.random.normal(jax.random.PRNGKey(5), (4,))
    ts_rev = jnp.array([0.6, 0.3, 0.0])   # monotone in integration direction

    def loss(x0, params, mode):
        ys = odeint(mlp_field, x0, params, t0=1.0, ts=ts_rev, method=method,
                    grad_mode=mode, n_steps=5)
        return jnp.sum(jnp.cos(ys))

    g_ref = jax.grad(loss, argnums=(0, 1))(x0, params, "backprop")
    g_sym = jax.grad(loss, argnums=(0, 1))(x0, params, "symplectic")
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sym)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("mode", ADAPTIVE_MODES)
def test_reverse_time_adaptive(mode):
    x0 = jnp.asarray([1.0, 0.5])
    cfg = AdaptiveConfig(rtol=1e-8, atol=1e-10, max_steps=128,
                         initial_step=0.05)
    y = odeint(linear, x0, LIN_P, t0=1.0, t1=0.0, method="dopri5",
               grad_mode=mode, adaptive=cfg)
    exact = x0 * jnp.exp(LIN_P["lam"] * (0.0 - 1.0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact), rtol=1e-6)


@pytest.mark.parametrize("mode", ALL_MODES)
def test_zero_length_interval_fixed(mode):
    params = make_params(jax.random.PRNGKey(6))
    x0 = jax.random.normal(jax.random.PRNGKey(7), (4,))
    y = odeint(mlp_field, x0, params, t0=0.5, t1=0.5, method="dopri5",
               grad_mode=mode, n_steps=4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x0),
                               rtol=0, atol=1e-15)
    # d(sum y)/d x0 == ones exactly: the zero-length map is the identity
    g = jax.grad(lambda x: jnp.sum(odeint(
        mlp_field, x, params, t0=0.5, t1=0.5, method="dopri5",
        grad_mode=mode, n_steps=4)))(x0)
    np.testing.assert_allclose(np.asarray(g), np.ones(4),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("mode", ADAPTIVE_MODES)
def test_zero_length_interval_adaptive(mode):
    params = make_params(jax.random.PRNGKey(8))
    x0 = jax.random.normal(jax.random.PRNGKey(9), (4,))
    cfg = AdaptiveConfig(max_steps=16, initial_step=0.1)
    y = odeint(mlp_field, x0, params, t0=0.5, t1=0.5, method="dopri5",
               grad_mode=mode, adaptive=cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x0),
                               rtol=0, atol=1e-15)
    if mode == "backprop":
        return  # reverse-mode through lax.while_loop is unsupported
    g = jax.grad(lambda x: jnp.sum(odeint(
        mlp_field, x, params, t0=0.5, t1=0.5, method="dopri5",
        grad_mode=mode, adaptive=cfg)))(x0)
    np.testing.assert_allclose(np.asarray(g), np.ones(4),
                               rtol=1e-12, atol=1e-12)


def test_saveat_repeated_observation_time():
    """A duplicate observation time is a zero-length segment: both rows
    observe the same state and gradients stay exact."""
    params = make_params(jax.random.PRNGKey(10))
    x0 = jax.random.normal(jax.random.PRNGKey(11), (4,))
    ts = jnp.array([0.5, 0.5, 1.0])

    def loss(x0, params, mode):
        ys = odeint(mlp_field, x0, params, ts=ts, method="bosh3",
                    grad_mode=mode, n_steps=4)
        return jnp.sum(jnp.sin(ys) ** 2)

    ys = odeint(mlp_field, x0, params, ts=ts, method="bosh3",
                grad_mode="symplectic", n_steps=4)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(ys[1]),
                               rtol=0, atol=1e-15)
    g_ref = jax.grad(loss, argnums=(0, 1))(x0, params, "backprop")
    g_sym = jax.grad(loss, argnums=(0, 1))(x0, params, "symplectic")
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_sym)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)


# --- adaptive-controller bugfixes --------------------------------------------

def test_controller_h_carry_survives_landing_clamp():
    """A tiny clamped landing step must not collapse the carried step: the
    controller bases continuations on the UNCLAMPED h."""
    tab = get_tableau("dopri5")
    cfg = AdaptiveConfig(rtol=1e-3, atol=1e-6, max_steps=64,
                         initial_step=0.25)
    # the final step is clamped to ~1e-9
    sol = rk_solve_adaptive(linear, tab, jnp.ones(2), 0.0, 0.5 + 1e-9,
                            LIN_P, cfg)
    assert bool(sol.succeeded)
    assert abs(float(sol.h_final)) > 0.1, float(sol.h_final)


def test_controller_h_threads_across_segments():
    """Segmented SaveAt solves seed each segment from the previous
    h_final: an observation time never resets the controller to
    initial_step."""
    tab = get_tableau("dopri5")
    cfg = AdaptiveConfig(rtol=1e-6, atol=1e-8, max_steps=64,
                         initial_step=1e-4)
    ts = jnp.array([0.5, 1.0])
    _, stacked = rk_solve_segments(linear, tab, jnp.ones(2), 0.0, ts, LIN_P,
                                   cfg)
    sols = [jax.tree_util.tree_map(lambda l: l[i], stacked)
            for i in range(ts.shape[0])]
    assert all(bool(s.succeeded) for s in sols)
    # without threading, segment 2 restarts at initial_step=1e-4 and needs
    # many doublings; with threading it continues at the grown step.
    assert int(sols[1].n_accepted) <= int(sols[0].n_accepted) // 2, \
        [int(s.n_accepted) for s in sols]


def test_controller_rejected_landing_clamp_keeps_h():
    """A REJECTED t1-clamped landing step must retry from the unclamped h,
    mirroring the accepted-step fix: shrinking from h_eff (the t1 gap)
    collapses the carried step to gap scale."""
    tab = get_tableau("dopri5")
    stiff = {"lam": jnp.asarray(-1e4)}
    # one attempt: the trial is clamped from 1.0 to the 1e-3 gap and
    # rejected (lam * h_eff = 10 >> 1), so h_final IS the retry step.
    cfg = AdaptiveConfig(rtol=1e-6, atol=1e-9, max_steps=8, max_attempts=1,
                         initial_step=1.0)
    sol = rk_solve_adaptive(linear, tab, jnp.ones(2), 0.0, 1e-3, stiff, cfg)
    assert not bool(sol.succeeded)
    assert int(sol.n_accepted) == 0            # the landing trial rejected
    # retry from the unclamped h: 1.0 * min_factor = 0.2.  The old update
    # retried from the clamped gap: 1e-3 * 0.2 = 2e-4.
    assert abs(float(sol.h_final)) > 0.1, float(sol.h_final)


def test_stiff_landing_interval_converges():
    """End-to-end stiff landing segment: geometric decay from the unclamped
    h still reaches the stable step and the solve lands accurately within
    the attempt budget (regression for the retry-base change)."""
    tab = get_tableau("dopri5")
    stiff = {"lam": jnp.asarray(-1e4)}
    cfg = AdaptiveConfig(rtol=1e-6, atol=1e-9, max_steps=256,
                         initial_step=1.0)
    sol = rk_solve_adaptive(linear, tab, jnp.ones(2), 0.0, 1e-3, stiff, cfg)
    assert bool(sol.succeeded)
    assert int(sol.n_attempts) < 120, int(sol.n_attempts)
    np.testing.assert_allclose(np.asarray(sol.x_final),
                               np.exp(-10.0) * np.ones(2), rtol=1e-4)


def test_direct_driver_time_cotangent_dtypes():
    """Drivers called directly (bypassing odeint's time coercion) must
    return time cotangents in the dtype the caller passed — here float32
    times under x64."""
    from repro.core import odeint_adjoint, symplectic_solve
    tab = get_tableau("dopri5")
    x0 = jnp.ones(3)
    t0, t1 = jnp.float32(0.0), jnp.float32(1.0)
    ts32 = jnp.array([0.5, 1.0], dtype=jnp.float32)
    cfg = AdaptiveConfig(max_steps=32, initial_step=0.1)

    cases = {
        "sym": (lambda a, b: jnp.sum(
            symplectic_solve(linear, tab, 6, "auto", x0, a, b, LIN_P)),
            (t0, t1)),
        "syma": (lambda a, b: jnp.sum(
            symplectic_solve(linear, tab, cfg, "auto", x0, a, b, LIN_P)),
            (t0, t1)),
        "adj": (lambda a, b: jnp.sum(
            odeint_adjoint(linear, tab, 6, 1, "auto", x0, a, b, LIN_P)),
            (t0, t1)),
        "sym_saveat": (lambda a, b: jnp.sum(
            symplectic_solve(linear, tab, 4, "auto", x0, a, b, LIN_P,
                             segmented=True)), (t0, ts32)),
        "syma_saveat": (lambda a, b: jnp.sum(
            symplectic_solve(linear, tab, cfg, "auto", x0, a, b, LIN_P,
                             segmented=True)), (t0, ts32)),
    }
    for name, (loss, targs) in cases.items():
        gts = jax.grad(loss, argnums=(0, 1))(*targs)
        for g, t in zip(gts, targs):
            assert g.dtype == t.dtype, (name, g.dtype, t.dtype)
            assert g.shape == t.shape, (name, g.shape, t.shape)
            np.testing.assert_array_equal(np.asarray(g),
                                          np.zeros(t.shape, t.dtype))


def test_time_resolution_dtype_aware():
    t32 = _time_resolution(jnp.float32(0.0), jnp.float32(1000.0),
                           jnp.float32)
    assert float(t32) > np.spacing(np.float32(1000.0))
    t64 = _time_resolution(jnp.float64(0.0), jnp.float64(1.0), jnp.float64)
    assert float(t64) < 1e-14   # far tighter than the old fixed threshold


@pytest.mark.slow   # subprocess with its own jax init/compile
def test_float32_termination_no_attempt_burn():
    """With x64 disabled the eps-scaled threshold terminates cleanly on
    typical and offset intervals (the old 1e-14 is below f32 resolution)."""
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        assert not jax.config.jax_enable_x64
        from repro.core import AdaptiveConfig, odeint_with_stats
        def f(x, t, p):
            return p["lam"] * x
        p = {"lam": jnp.asarray(-2.0)}
        cfg = AdaptiveConfig(rtol=1e-4, atol=1e-6, max_steps=256,
                             initial_step=0.05)
        for (a, b) in [(0.0, 1.0), (1000.0, 1001.0), (-0.5, 0.5)]:
            y, st = odeint_with_stats(f, jnp.ones(3), p, t0=a, t1=b,
                                      adaptive=cfg)
            assert bool(st["succeeded"]), (a, b)
            assert int(st["n_attempts"]) < 200, (a, b,
                                                 int(st["n_attempts"]))
            assert bool(jnp.all(jnp.isfinite(y))), (a, b)
        print("OK")
    """)
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "0"
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_failure_flag_and_poisoning():
    cfg = AdaptiveConfig(rtol=1e-14, atol=1e-16, max_steps=4,
                         initial_step=0.01)
    x0 = jnp.asarray([1.0, 2.0])
    _, stats = odeint_with_stats(linear, x0, LIN_P, t1=1.0,
                                 method="dopri5", adaptive=cfg)
    assert not bool(stats["succeeded"])
    for mode in ADAPTIVE_MODES:
        y = odeint(linear, x0, LIN_P, t1=1.0, method="dopri5",
                   grad_mode=mode, adaptive=cfg)
        assert bool(jnp.all(jnp.isnan(y))), mode
    cfg_ok = AdaptiveConfig(rtol=1e-14, atol=1e-16, max_steps=4,
                            initial_step=0.01, on_failure="ignore")
    y = odeint(linear, x0, LIN_P, t1=1.0, method="dopri5",
               grad_mode="symplectic", adaptive=cfg_ok)
    assert bool(jnp.all(jnp.isfinite(y)))
    with pytest.raises(ValueError, match="on_failure"):
        AdaptiveConfig(on_failure="explode")


def test_failure_raise_policy():
    cfg = AdaptiveConfig(rtol=1e-14, atol=1e-16, max_steps=4,
                         initial_step=0.01, on_failure="raise")
    with pytest.raises(Exception, match="max_steps/max_attempts"):
        y = odeint(linear, jnp.ones(2), LIN_P, t1=1.0, method="dopri5",
                   grad_mode="backprop", adaptive=cfg)
        jax.block_until_ready(y)
