"""Composable solve() API tests.

Covers the api_redesign acceptance criteria:
  * ``Solution`` is a well-behaved pytree: round-trips through ``jit``,
    ``vmap`` over batched x0, and ``jax.grad`` of losses on ``sol.ys``;
  * golden equivalence: the ``odeint`` / ``odeint_with_stats`` shims pin
    EXACTLY (values and stats dicts) to the pre-redesign behavior — i.e.
    to the unchanged underlying drivers and the historical stats formulas —
    for all five gradient modes on fixed and adaptive grids; the
    symplectic value is the plain stepper solve bitwise, and its gradient
    is DirectBackprop's to rounding;
  * the one symplectic driver's gradient matches jax.grad through
    DirectBackprop in every (stepping, observation, lanes) cell;
  * a new gradient strategy registers and solves WITHOUT editing solve();
  * the declarative capability matrix rejects every illegal combination
    with a uniform error;
  * the satellite validations: eager ts-monotonicity rejection and
    ContinuousAdjoint.steps_multiplier >= 1 (also via the legacy kwarg).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core import (AdaptiveConfig, ContinuousAdjoint, DirectBackprop,
                        GRAD_MODES, RematSolve, RematStep, SaveAt, Solution,
                        SymplecticAdjoint, apply_on_failure, as_gradient,
                        capability_matrix, get_tableau, hermite_observe,
                        odeint, odeint_adjoint, odeint_adjoint_adaptive,
                        odeint_backprop, odeint_remat_solve,
                        odeint_remat_step, odeint_with_stats,
                        register_gradient, rk_solve_adaptive, rk_solve_fixed,
                        rk_solve_segments, rk_step, solve)
from repro.core import api as api_mod


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


PARAMS = make_params(jax.random.PRNGKey(0))
X0 = jax.random.normal(jax.random.PRNGKey(1), (4,))
TS3 = jnp.array([0.25, 0.5, 0.875])
CFG = AdaptiveConfig(rtol=1e-6, atol=1e-8, max_steps=64, initial_step=0.05)
TAB = get_tableau("dopri5")


def assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def shim_odeint(*args, **kwargs):
    with pytest.warns(DeprecationWarning, match="odeint-style"):
        return odeint(*args, **kwargs)


def shim_with_stats(*args, **kwargs):
    with pytest.warns(DeprecationWarning, match="odeint-style"):
        return odeint_with_stats(*args, **kwargs)


# --- Solution as a pytree ----------------------------------------------------

def test_solution_jit_round_trip():
    def run(x0):
        return solve(mlp_field, x0, PARAMS, stepping=6)

    sol = run(X0)
    jsol = jax.jit(run)(X0)
    assert isinstance(jsol, Solution)
    np.testing.assert_allclose(np.asarray(jsol.ys), np.asarray(sol.ys),
                               rtol=1e-14)  # jit may refuse by 1 ulp
    assert_trees_equal(sol.stats, jsol.stats)
    assert bool(jsol.success)
    # flatten/unflatten identity
    leaves, treedef = jax.tree_util.tree_flatten(sol)
    sol2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(sol2, Solution)
    assert_trees_equal(sol.final_state, sol2.final_state)


@pytest.mark.parametrize("stepping", [6, CFG], ids=["fixed", "adaptive"])
def test_solution_vmap_batched_x0(stepping):
    xb = jnp.stack([X0, 2.0 * X0, -X0])
    vsol = jax.vmap(lambda x: solve(mlp_field, x, PARAMS,
                                    stepping=stepping))(xb)
    assert vsol.ys.shape == (3, 4)
    assert vsol.stats["n_steps"].shape == (3,)
    assert vsol.success.shape == (3,)
    for i in range(3):
        one = solve(mlp_field, xb[i], PARAMS, stepping=stepping)
        np.testing.assert_allclose(np.asarray(vsol.ys[i]),
                                   np.asarray(one.ys), rtol=1e-12)
        assert int(vsol.stats["n_steps"][i]) == int(one.stats["n_steps"])


def test_solution_grad_on_ys():
    def loss(x0, params, gradient):
        sol = solve(mlp_field, x0, params, saveat=SaveAt(ts=TS3),
                    gradient=gradient, stepping=5)
        return jnp.sum(jnp.sin(sol.ys) ** 2)

    g_sym = jax.grad(loss, argnums=(0, 1))(X0, PARAMS, SymplecticAdjoint())
    g_ref = jax.grad(loss, argnums=(0, 1))(X0, PARAMS, DirectBackprop())
    for a, b in zip(jax.tree_util.tree_leaves(g_sym),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)


def test_solution_stats_fixed_are_static_counts():
    sol = solve(mlp_field, X0, PARAMS, method="dopri5", stepping=7)
    assert int(sol.stats["n_steps"]) == 7
    assert int(sol.stats["n_fevals"]) == 7 * TAB.s
    assert int(sol.stats["n_attempts"]) == 7
    sol = solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=TS3), stepping=4)
    assert int(sol.stats["n_steps"]) == 3 * 4
    assert_trees_equal(sol.final_state, sol.ys[-1])


def test_solution_stats_adaptive_match_controller():
    ref = rk_solve_adaptive(mlp_field, TAB, X0, jnp.asarray(0.0), 1.0,
                            PARAMS, CFG)
    for gradient in (SymplecticAdjoint(), DirectBackprop(),
                     ContinuousAdjoint()):
        sol = solve(mlp_field, X0, PARAMS, gradient=gradient, stepping=CFG)
        assert int(sol.stats["n_steps"]) == int(ref.n_accepted)
        assert int(sol.stats["n_fevals"]) == int(ref.n_fevals)
        assert int(sol.stats["n_attempts"]) == int(ref.n_attempts)
        assert bool(sol.success)


# --- golden equivalence: shims == pre-redesign drivers -----------------------

FIXED_DRIVERS = {
    # the symplectic value is the plain stepper solve, bit for bit
    "symplectic": lambda n, x, t0, t1: rk_solve_fixed(
        mlp_field, TAB, x, t0, t1, n, PARAMS).x_final,
    "backprop": lambda n, x, t0, t1: odeint_backprop(
        mlp_field, TAB, n, x, t0, t1, PARAMS, "auto"),
    "remat_step": lambda n, x, t0, t1: odeint_remat_step(
        mlp_field, TAB, n, x, t0, t1, PARAMS, "auto"),
    "remat_solve": lambda n, x, t0, t1: odeint_remat_solve(
        mlp_field, TAB, n, x, t0, t1, PARAMS, "auto"),
    "adjoint": lambda n, x, t0, t1: odeint_adjoint(
        mlp_field, TAB, n, 1, "auto", x, t0, t1, PARAMS),
}


def lane_field(x, t, params):
    """``mlp_field`` row-wise: a (B, 4) state is B states."""
    h = jnp.tanh(x @ params["w1"].T + params["b1"] + t)
    return h @ params["w2"].T + params["b2"]


def _grid_replay(f, x0, params, saveat, stepping, lanes):
    """DirectBackprop on the realized grid: a differentiable re-run of the
    solve's own accepted steps (``rk_step`` along the recorded rows, rows
    past ``n_accepted`` leaving the state unchanged), per lane on lanes.
    An adaptive solve's while_loop admits no reverse mode, and its gradient
    is that of the discrete map along the grid it realized."""
    ts = TS3 if saveat.kind == "ts" else jnp.asarray([saveat.t1])
    _, sols = rk_solve_segments(f, TAB, jax.lax.stop_gradient(x0),
                                0.0, ts, jax.lax.stop_gradient(params),
                                stepping, lanes=lanes)

    def segment(x, ts_k, hs_k, n_k):
        def body(x, row):
            t, h, live = row
            x_next, _ = rk_step(f, TAB, x, t, h, params)
            return jnp.where(live, x_next, x), None

        live = jnp.arange(ts_k.shape[0]) < n_k
        return jax.lax.scan(body, x, (ts_k, hs_k, live))[0]

    def trajectory(x, ts_l, hs_l, n_l):
        ys = []
        for k in range(ts.shape[0]):
            x = segment(x, ts_l[k], hs_l[k], n_l[k])
            ys.append(x)
        return jnp.stack(ys)

    if lanes:
        ys = jax.vmap(trajectory, in_axes=(0, 2, 2, 1), out_axes=1)(
            x0, sols.ts, sols.hs, sols.n_accepted)
    else:
        ys = trajectory(x0, sols.ts, sols.hs, sols.n_accepted)
    return ys[-1] if saveat.kind == "t1" else ys


def assert_symplectic_grad_exact(saveat, stepping, x0=X0, batch_axis=None,
                                 f=mlp_field):
    """jax.grad through the one symplectic driver == jax.grad through
    DirectBackprop (on an adaptive solve's realized grid), to rounding."""
    def loss(x0, params, gradient):
        sol = solve(f, x0, params, saveat=saveat, gradient=gradient,
                    stepping=stepping, batch_axis=batch_axis)
        return jnp.sum(jnp.sin(sol.ys) ** 2)

    g_sym = jax.grad(loss, argnums=(0, 1))(x0, PARAMS, SymplecticAdjoint())
    if isinstance(stepping, AdaptiveConfig):
        g_ref = jax.grad(
            lambda x, p: jnp.sum(jnp.sin(_grid_replay(
                f, x, p, saveat, stepping, batch_axis is not None)) ** 2),
            argnums=(0, 1))(x0, PARAMS)
    else:
        g_ref = jax.grad(loss, argnums=(0, 1))(x0, PARAMS, DirectBackprop())
    for a, b in zip(jax.tree_util.tree_leaves(g_sym),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-9, atol=1e-11)
    return g_sym


@pytest.mark.parametrize("mode", list(GRAD_MODES))
def test_golden_fixed_t1(mode):
    y = shim_odeint(mlp_field, X0, PARAMS, t1=1.0, method="dopri5",
                    grad_mode=mode, n_steps=6)
    t0 = jnp.asarray(0.0)
    ref = FIXED_DRIVERS[mode](6, X0, t0, jnp.asarray(1.0))
    assert_trees_equal(y, ref)
    # and the new entry point is the same map
    sol = solve(mlp_field, X0, PARAMS, saveat=SaveAt(t1=1.0),
                gradient=mode, stepping=6)
    assert_trees_equal(y, sol.ys)
    assert_trees_equal(sol.ys, sol.final_state)
    if mode == "symplectic":
        assert_symplectic_grad_exact(SaveAt(t1=1.0), 6)


@pytest.mark.parametrize("mode", list(GRAD_MODES))
def test_golden_fixed_ts_segmented(mode):
    ys = shim_odeint(mlp_field, X0, PARAMS, ts=TS3, method="dopri5",
                     grad_mode=mode, n_steps=4)
    if mode == "symplectic":
        ref, _ = rk_solve_segments(mlp_field, TAB, X0, jnp.asarray(0.0), TS3,
                                   PARAMS, 4)
        assert_trees_equal(ys, ref)
        assert_symplectic_grad_exact(SaveAt(ts=TS3), 4)
    else:
        # pre-redesign: chained per-segment driver solves
        x, t_prev = X0, jnp.asarray(0.0)
        for i in range(3):
            x = FIXED_DRIVERS[mode](4, x, t_prev, TS3[i])
            np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(x),
                                       rtol=1e-12, atol=1e-14)
            t_prev = TS3[i]


@pytest.mark.parametrize("mode", ["symplectic", "backprop", "adjoint"])
def test_golden_adaptive_t1(mode):
    y = shim_odeint(mlp_field, X0, PARAMS, t1=1.0, method="dopri5",
                    grad_mode=mode, adaptive=CFG)
    t0, t1 = jnp.asarray(0.0), jnp.asarray(1.0)
    if mode == "adjoint":
        ref = odeint_adjoint_adaptive(mlp_field, TAB, CFG, CFG, "auto",
                                      X0, t0, t1, PARAMS)
    else:
        sol = rk_solve_adaptive(mlp_field, TAB, X0, t0, t1, PARAMS, CFG)
        ref = apply_on_failure(sol.x_final, sol.succeeded, CFG.on_failure)
    assert_trees_equal(y, ref)
    if mode == "symplectic":
        assert_symplectic_grad_exact(SaveAt(t1=1.0), CFG)


@pytest.mark.parametrize("mode", ["symplectic", "backprop", "adjoint"])
def test_golden_adaptive_ts(mode):
    ys = shim_odeint(mlp_field, X0, PARAMS, ts=TS3, method="dopri5",
                     grad_mode=mode, adaptive=CFG)
    assert ys.shape == (3, 4)
    if mode == "symplectic":
        ref, _ = rk_solve_segments(mlp_field, TAB, X0, jnp.asarray(0.0), TS3,
                                   PARAMS, CFG)
        assert_trees_equal(ys, ref)
        assert_symplectic_grad_exact(SaveAt(ts=TS3), CFG)
    elif mode == "adjoint":
        # pre-redesign: per-segment odeint_adjoint_adaptive (controller
        # RESTARTS at each observation boundary)
        x, t_prev = X0, jnp.asarray(0.0)
        for i in range(3):
            x = odeint_adjoint_adaptive(mlp_field, TAB, CFG, CFG, "auto",
                                        x, t_prev, TS3[i], PARAMS)
            np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(x),
                                       rtol=1e-12, atol=1e-14)
            t_prev = TS3[i]


def test_golden_symplectic_gradient_through_shim():
    def loss_shim(x0, params):
        y = odeint(mlp_field, x0, params, t1=1.0, method="dopri5",
                   grad_mode="symplectic", n_steps=6)
        return jnp.sum(jnp.tanh(y) ** 2)

    def loss_solve(x0, params, gradient):
        y = solve(mlp_field, x0, params, saveat=SaveAt(t1=1.0),
                  gradient=gradient, stepping=6).ys
        return jnp.sum(jnp.tanh(y) ** 2)

    with pytest.warns(DeprecationWarning, match="odeint-style"):
        g_shim = jax.grad(loss_shim, argnums=(0, 1))(X0, PARAMS)
    g_sym = jax.grad(loss_solve, argnums=(0, 1))(X0, PARAMS,
                                                 SymplecticAdjoint())
    assert_trees_equal(g_shim, g_sym)
    g_ref = jax.grad(loss_solve, argnums=(0, 1))(X0, PARAMS,
                                                 DirectBackprop())
    for a, b in zip(jax.tree_util.tree_leaves(g_sym),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-10, atol=1e-12)


# --- the one symplectic driver, every cell -----------------------------------

X0_LANES = jnp.stack([X0, 2.0 * X0, -0.5 * X0])


@pytest.mark.parametrize("lanes", [False, True], ids=["single", "lanes"])
@pytest.mark.parametrize("saveat", [SaveAt(t1=1.0), SaveAt(ts=TS3)],
                         ids=["t1", "ts"])
@pytest.mark.parametrize("stepping", [6, CFG], ids=["fixed", "adaptive"])
def test_symplectic_driver_gradient_matches_backprop(stepping, saveat, lanes):
    x0 = X0_LANES if lanes else X0
    g = assert_symplectic_grad_exact(saveat, stepping, x0=x0,
                                     batch_axis=0 if lanes else None,
                                     f=lane_field)
    if lanes and not isinstance(stepping, AdaptiveConfig):
        # a fixed grid cannot depend on the state: the lanes are one state
        # to the replay, never masked, so the gradient is the unbatched
        # solve's bit for bit
        assert_trees_equal(g, assert_symplectic_grad_exact(
            saveat, stepping, x0=x0, f=lane_field))


def test_golden_with_stats_fixed():
    y, stats = shim_with_stats(mlp_field, X0, PARAMS, t1=1.0,
                               method="dopri5", n_steps=5)
    assert sorted(stats) == ["n_fevals", "n_steps"]
    assert int(stats["n_steps"]) == 5
    assert int(stats["n_fevals"]) == 5 * TAB.s
    assert_trees_equal(y, FIXED_DRIVERS["backprop"](
        5, X0, jnp.asarray(0.0), jnp.asarray(1.0)))

    ys, stats = shim_with_stats(mlp_field, X0, PARAMS, ts=TS3,
                                method="dopri5", n_steps=5)
    assert sorted(stats) == ["n_fevals", "n_steps"]
    assert int(stats["n_steps"]) == 3 * 5
    assert int(stats["n_fevals"]) == 3 * 5 * TAB.s


def test_golden_with_stats_adaptive():
    y, stats = shim_with_stats(mlp_field, X0, PARAMS, t1=1.0,
                               method="dopri5", adaptive=CFG)
    sol = rk_solve_adaptive(mlp_field, TAB, X0, jnp.asarray(0.0), 1.0,
                            PARAMS, CFG)
    assert sorted(stats) == ["n_attempts", "n_fevals", "n_steps",
                             "succeeded"]
    assert int(stats["n_steps"]) == int(sol.n_accepted)
    assert int(stats["n_fevals"]) == int(sol.n_fevals)
    assert int(stats["n_attempts"]) == int(sol.n_attempts)
    assert bool(stats["succeeded"])
    assert_trees_equal(y, sol.x_final)


def test_golden_with_stats_adaptive_ts_is_dense():
    ys, stats = shim_with_stats(mlp_field, X0, PARAMS, ts=TS3,
                                method="dopri5", adaptive=CFG)
    sol = rk_solve_adaptive(mlp_field, TAB, X0, jnp.asarray(0.0), TS3[-1],
                            PARAMS, CFG)
    ref = hermite_observe(mlp_field, TAB, sol, PARAMS, TS3)
    assert_trees_equal(ys, ref)
    assert int(stats["n_steps"]) == int(sol.n_accepted)
    assert int(stats["n_fevals"]) == int(sol.n_fevals) + 2 * 3


def test_golden_with_stats_failure_not_poisoned():
    # historical contract: with_stats NEVER poisons/raises — failure is
    # reported via stats["succeeded"], even when the config says otherwise.
    tight = AdaptiveConfig(rtol=1e-14, atol=1e-16, max_steps=4,
                           initial_step=0.01, on_failure="nan")
    y, stats = shim_with_stats(mlp_field, X0, PARAMS, t1=1.0,
                               method="dopri5", adaptive=tight)
    assert not bool(stats["succeeded"])
    assert bool(jnp.all(jnp.isfinite(y)))


# --- extensible gradient registry --------------------------------------------

def test_register_toy_strategy_without_editing_solve():
    @register_gradient
    @dataclasses.dataclass(frozen=True)
    class ToyDoubleSteps(api_mod.GradientStrategy):
        """Backprop with a doubled step budget — three lines of hooks."""
        name = "toy_double"
        capabilities = frozenset({("fixed", "t1"), ("fixed", "ts")})

        def fixed(self, ctx, x0, t0, t1, params):
            return odeint_backprop(ctx.f, ctx.tab, 2 * ctx.n_steps,
                                   x0, t0, t1, params, ctx.backend)

    try:
        sol = solve(mlp_field, X0, PARAMS, gradient="toy_double",
                    stepping=3)
        ref = solve(mlp_field, X0, PARAMS, gradient="backprop", stepping=6)
        assert_trees_equal(sol.ys, ref.ys)
        # the default SaveAt segmentation comes for free
        sol = solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=TS3),
                    gradient=ToyDoubleSteps(), stepping=3)
        assert sol.ys.shape == (3, 4)
        # and the capability matrix guards the cells it did not claim
        with pytest.raises(ValueError, match="toy_double"):
            solve(mlp_field, X0, PARAMS, gradient="toy_double",
                  stepping=CFG)
        assert "toy_double" in capability_matrix()
    finally:
        del api_mod.GRADIENT_REGISTRY["toy_double"]


def test_minimal_adaptive_strategy_stats_match_its_own_solve():
    """A strategy implementing ONLY adaptive() gets SaveAt values from the
    default restart-per-segment segmentation — and the default stats
    replay must describe that same restarting sequence, not a threaded
    one."""
    @register_gradient
    @dataclasses.dataclass(frozen=True)
    class ToyAdaptive(api_mod.GradientStrategy):
        name = "toy_adaptive"
        capabilities = frozenset({("adaptive", "t1"), ("adaptive", "ts")})

        def adaptive(self, ctx, x0, t0, t1, params):
            sol = rk_solve_adaptive(ctx.f, ctx.tab, x0, t0, t1, params,
                                    ctx.adaptive, ctx.backend)
            return apply_on_failure(sol.x_final, sol.succeeded,
                                    ctx.adaptive.on_failure)

    try:
        sol = solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=TS3),
                    gradient="toy_adaptive", stepping=CFG)
        # reference: replay the restarting segmentation by hand
        x, t_prev, n_acc = X0, jnp.asarray(0.0), 0
        for i in range(3):
            seg = rk_solve_adaptive(mlp_field, TAB, x, t_prev, TS3[i],
                                    PARAMS, CFG)
            x, t_prev, n_acc = seg.x_final, TS3[i], n_acc + int(seg.n_accepted)
            np.testing.assert_allclose(np.asarray(sol.ys[i]),
                                       np.asarray(x), rtol=1e-12)
        assert int(sol.stats["n_steps"]) == n_acc
        assert bool(sol.success)
    finally:
        del api_mod.GRADIENT_REGISTRY["toy_adaptive"]


def test_as_gradient_spec_forms():
    assert isinstance(as_gradient("symplectic"), SymplecticAdjoint)
    assert isinstance(as_gradient(DirectBackprop), DirectBackprop)
    adj = ContinuousAdjoint(steps_multiplier=3)
    assert as_gradient(adj) is adj
    with pytest.raises(ValueError, match="unknown gradient strategy"):
        as_gradient("nope")
    with pytest.raises(TypeError):
        as_gradient(42)


# --- capability matrix -------------------------------------------------------

def test_capability_matrix_shape_and_errors():
    mat = capability_matrix()
    for name in GRAD_MODES:
        assert name in mat
        assert len(mat[name]) == 6  # 2 steppings x 3 saveat kinds
        assert not mat[name][("fixed", "dense")]  # dense needs a controller
    assert mat["backprop"][("adaptive", "dense")]
    for bad_gradient, stepping, saveat in [
            (RematStep(), CFG, None),
            (RematSolve(), CFG, None),
            (RematStep(), CFG, SaveAt(ts=TS3)),
            (SymplecticAdjoint(), CFG, SaveAt(ts=TS3, dense=True)),
            (DirectBackprop(), 4, SaveAt(ts=TS3, dense=True))]:
        with pytest.raises(ValueError,
                           match="legal .stepping.saveat. combinations"):
            solve(mlp_field, X0, PARAMS, saveat=saveat,
                  gradient=bad_gradient, stepping=stepping)


def test_stepping_validation():
    with pytest.raises(ValueError, match="needs >= 1 steps"):
        solve(mlp_field, X0, PARAMS, stepping=0)
    with pytest.raises(TypeError, match="stepping must be"):
        solve(mlp_field, X0, PARAMS, stepping="adaptive")


def test_saveat_validation():
    with pytest.raises(ValueError, match="EITHER t1 or ts"):
        SaveAt(t1=1.0, ts=TS3)
    with pytest.raises(ValueError, match="one of t1"):
        SaveAt()
    with pytest.raises(ValueError, match="dense"):
        SaveAt(t1=1.0, dense=True)
    with pytest.raises(ValueError, match="EITHER t1 or ts"):
        shim_odeint(mlp_field, X0, PARAMS, t1=1.0, ts=TS3)


# --- satellite: ts monotonicity contract -------------------------------------

def test_ts_rejects_descending_against_direction():
    # forward t0 but descending ts: direction flips mid-solve
    with pytest.raises(ValueError, match="monotone"):
        solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=jnp.array(
            [0.875, 0.5, 0.25])), stepping=4, t0=0.0)


def test_ts_rejects_shuffled():
    for bad in ([0.5, 0.25, 0.875], [0.25, 0.875, 0.5]):
        with pytest.raises(ValueError, match="monotone"):
            solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=jnp.array(bad)),
                  stepping=4)
        with pytest.raises(ValueError, match="monotone"):
            shim_odeint(mlp_field, X0, PARAMS, ts=jnp.array(bad), n_steps=4)


def test_ts_allows_duplicates_and_reverse_time():
    sol = solve(mlp_field, X0, PARAMS,
                saveat=SaveAt(ts=jnp.array([0.5, 0.5, 1.0])), stepping=4)
    assert_trees_equal(sol.ys[0], sol.ys[1])
    sol = solve(mlp_field, X0, PARAMS,
                saveat=SaveAt(ts=jnp.array([0.6, 0.3, 0.0])), stepping=4,
                t0=1.0)
    assert sol.ys.shape == (3, 4)


def test_ts_tracer_passes_through():
    # non-concrete ts cannot be validated at trace time; the solve must
    # still trace and run (the contract is on the caller).
    ys = jax.jit(lambda ts: solve(mlp_field, X0, PARAMS,
                                  saveat=SaveAt(ts=ts),
                                  stepping=4).ys)(TS3)
    ref = solve(mlp_field, X0, PARAMS, saveat=SaveAt(ts=TS3), stepping=4).ys
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref), rtol=1e-12)


# --- satellite: ContinuousAdjoint.steps_multiplier >= 1 ----------------------

def test_adjoint_steps_multiplier_validation():
    with pytest.raises(ValueError, match="steps_multiplier"):
        ContinuousAdjoint(steps_multiplier=0)
    with pytest.raises(ValueError, match="steps_multiplier"):
        ContinuousAdjoint(steps_multiplier=-2)
    assert ContinuousAdjoint(steps_multiplier=2).steps_multiplier == 2
    # numpy integers (configs, loaded arrays) are normalized, like
    # solve()'s stepping
    adj = ContinuousAdjoint(steps_multiplier=np.int64(2))
    assert adj.steps_multiplier == 2 and type(adj.steps_multiplier) is int
    with pytest.raises(ValueError, match="steps_multiplier"):
        ContinuousAdjoint(steps_multiplier=np.int64(0))
    # the legacy kwarg funnels through the same check
    with pytest.raises(ValueError, match="steps_multiplier"):
        shim_odeint(mlp_field, X0, PARAMS, t1=1.0, grad_mode="adjoint",
                    adjoint_steps_multiplier=0)
    # historical contract: the adjoint-only kwargs are ignored by other
    # modes, so a bogus multiplier must NOT trip them
    y = shim_odeint(mlp_field, X0, PARAMS, t1=1.0, grad_mode="symplectic",
                    adjoint_steps_multiplier=0, n_steps=4)
    assert bool(jnp.all(jnp.isfinite(y)))


# --- deprecation surface -----------------------------------------------------

def test_shims_warn_deprecation():
    with pytest.warns(DeprecationWarning, match="repro.core.solve"):
        odeint(mlp_field, X0, PARAMS, t1=1.0, n_steps=2)
    with pytest.warns(DeprecationWarning, match="repro.core.solve"):
        odeint_with_stats(mlp_field, X0, PARAMS, t1=1.0, n_steps=2)
