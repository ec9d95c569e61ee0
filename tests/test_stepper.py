"""Pause/resume exactness of the explicit SolverState stepper (ISSUE 8).

The drivers are thin loops over ``AdaptiveStepper.advance`` /
``FixedStepper.advance``; this file pins the property that makes the
state machine worth having: a solve driven one ``advance`` at a time —
with the state flattened/unflattened and round-tripped through a
simulated save/restore (device -> host numpy -> device) mid-trajectory —
reproduces the UNINTERRUPTED solve BIT-FOR-BIT: final state, accepted
grids, stats, and the symplectic-adjoint gradients replayed from those
grids.  This is the contract the continuous-batching serve engine (and
any checkpointed long solve) stands on: pausing never perturbs the
numbers.

Cross-PROGRAM equality (a per-call jitted ``advance`` vs the fused
``lax.while_loop`` driver body) is additionally bitwise wherever XLA's
codegen is stable across those two compilation contexts — empirically
the lane-batched adaptive path and the fixed-grid path.  The scalar
adaptive path fuses differently inside a while body than standalone
(FMA/fusion choices on rank-0 ops), so there the driver comparison pins
integer stats exactly and floats to ~1 ulp-per-step accumulation; the
bit-for-bit pause/resume guarantee is unaffected (both sides of it run
the same executable).
"""
import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import AdaptiveConfig
from repro.core.rk import (rk_solve_adaptive, rk_solve_adaptive_batched,
                           rk_solve_fixed)
from repro.core.stepper import AdaptiveStepper, FixedStepper
from repro.core.symplectic import symplectic_replay, symplectic_solve
from repro.core.tableau import get_tableau

TAB = get_tableau("dopri5")
CFG = AdaptiveConfig(rtol=1e-6, atol=1e-8, max_steps=64, initial_step=0.05)
T0, T1 = 0.0, 1.0
DIM, B = 3, 4

PARAMS = {"w": jax.random.normal(jax.random.PRNGKey(0), (DIM, DIM)) * 0.5,
          "b": jax.random.normal(jax.random.PRNGKey(1), (DIM,)) * 0.1}
X0 = jax.random.normal(jax.random.PRNGKey(2), (DIM,))
X0_LANES = jax.random.normal(jax.random.PRNGKey(3), (B, DIM))
T1_LANES = jnp.linspace(0.6, 1.4, B)


def field(x, t, p):
    return jnp.tanh(x @ p["w"] + p["b"]) - 0.3 * x * jnp.sin(t)


def loss(x):
    return jnp.sum(jnp.sin(x) ** 2)


def tree_bits_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def tree_allclose(a, b, tol=1e-10):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.allclose(np.asarray(x), np.asarray(y), rtol=tol, atol=tol)
               for x, y in zip(la, lb))


def save_restore(state):
    """Simulated checkpoint: flatten, pull every leaf to host numpy (as a
    serializer would), rebuild the pytree from the host copies."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    host = [np.asarray(jax.device_get(l)) for l in leaves]
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(h) for h in host])


def drive(stepper, state, params, pause_after=None):
    """Run ``advance`` one jitted call at a time, optionally interrupting
    with a save/restore round-trip after ``pause_after`` attempted steps.
    Returns (final_state, n_calls)."""
    adv = jax.jit(stepper.advance)
    steps = 0
    while not bool(stepper.is_done(state)):
        state = adv(state, params)
        steps += 1
        if steps == pause_after:
            state = save_restore(state)
        assert steps < 10_000
    return state, steps


# ---------------------------------------------------------------------------
# adaptive, single trajectory
# ---------------------------------------------------------------------------

def test_adaptive_pause_resume_bit_exact():
    stepper = AdaptiveStepper(field, TAB, CFG)
    uninterrupted, n = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS)
    assert n > 4                    # enough steps for a mid-flight pause
    paused, _ = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS,
                      pause_after=3)
    assert tree_bits_equal(uninterrupted, paused)
    sol = stepper.finalize(paused)
    assert bool(sol.succeeded)
    assert int(sol.n_accepted) > 3


def test_adaptive_stepper_matches_driver():
    """The per-call advance drive vs the fused while_loop driver: stats and
    grids agree (floats to tight tolerance — XLA fuses rank-0 math
    differently inside a while body than in a standalone executable)."""
    one_shot = rk_solve_adaptive(field, TAB, X0, T0, T1, PARAMS, CFG)
    stepper = AdaptiveStepper(field, TAB, CFG)
    state, _ = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS)
    sol = stepper.finalize(state)
    for f in ("n_accepted", "n_fevals", "n_attempts", "succeeded"):
        assert np.array_equal(np.asarray(getattr(one_shot, f)),
                              np.asarray(getattr(sol, f))), f
    for f in ("x_final", "xs", "ts", "hs", "h_final"):
        assert np.allclose(np.asarray(getattr(one_shot, f)),
                           np.asarray(getattr(sol, f)),
                           rtol=1e-9, atol=1e-9), f


def test_adaptive_pause_resume_gradients_bit_exact():
    stepper = AdaptiveStepper(field, TAB, CFG)

    def replay(state):
        sol = stepper.finalize(state)
        lam_N = jax.grad(loss)(sol.x_final)
        ckpt = (sol.xs, sol.ts, sol.hs, sol.n_accepted)
        return symplectic_replay(field, TAB, ckpt, PARAMS, lam_N)

    uninterrupted, _ = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS)
    paused, _ = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS,
                      pause_after=3)
    g_full = replay(uninterrupted)
    g_paused = replay(paused)
    assert tree_bits_equal(g_full, g_paused)

    # and the replayed gradient agrees with end-to-end jax.grad through
    # the driver (same checkpoints up to the while-body fusion ulps)
    g_one = jax.grad(
        lambda x0, p: loss(symplectic_solve(
            field, TAB, CFG, "auto", x0, T0, T1, p)),
        argnums=(0, 1))(X0, PARAMS)
    assert tree_allclose(g_one[0], g_paused[0], tol=1e-8)
    assert tree_allclose(g_one[1], g_paused[1], tol=1e-8)


def test_tolerances_as_data_bit_match_closed_floats():
    """Per-solve rtol/atol ARRAYS (the serve engine's tolerances-as-data
    path) must reproduce the closed-Python-float solve exactly — grids,
    stats, and controller trajectory — through the same advance
    executable."""
    stepper = AdaptiveStepper(field, TAB, CFG)
    closed, _ = drive(stepper, stepper.init_state(X0, T0, T1), PARAMS)
    as_data = stepper.init_state(X0, T0, T1, rtol=CFG.rtol, atol=CFG.atol)
    assert as_data.rtol is not None
    adv = jax.jit(stepper.advance)
    while not bool(stepper.is_done(as_data)):
        as_data = adv(as_data, PARAMS)
    # rtol/atol ride along in the state; compare everything else
    drop = lambda s: s._replace(rtol=None, atol=None)
    assert tree_bits_equal(drop(closed), drop(as_data))


def test_advance_past_done_is_identity():
    """Driving ``advance`` beyond completion must not move the state — the
    serve engine relies on this to keep finished/free lanes frozen inside
    a running batch."""
    stepper = AdaptiveStepper(field, TAB, CFG)
    state = stepper.run(stepper.init_state(X0, T0, T1), PARAMS)
    assert bool(stepper.is_done(state))
    again = stepper.advance(state, PARAMS)
    assert tree_bits_equal(state, again)


# ---------------------------------------------------------------------------
# adaptive, lane-batched (the serve engine's path: cross-program bitwise)
# ---------------------------------------------------------------------------

def test_batched_pause_resume_bit_exact():
    one_shot = rk_solve_adaptive_batched(field, TAB, X0_LANES, T0, T1_LANES,
                                         PARAMS, CFG)
    stepper = AdaptiveStepper(field, TAB, CFG)
    state, _ = drive(stepper,
                     stepper.init_state(X0_LANES, T0, T1_LANES, lanes=B),
                     PARAMS, pause_after=3)
    resumed = stepper.finalize(state)
    assert tree_bits_equal(one_shot._asdict(), resumed._asdict())
    assert bool(jnp.all(resumed.succeeded))
    # heterogeneous horizons: lanes finish at different step counts, so the
    # pause caught some lanes mid-flight and others done
    assert len(set(np.asarray(resumed.n_accepted).tolist())) > 1


def test_batched_pause_resume_gradients_bit_exact():
    g_one = jax.grad(
        lambda x0, p: loss(symplectic_solve(
            field, TAB, CFG, "auto", x0, T0, T1_LANES, p, lanes=True)),
        argnums=(0, 1))(X0_LANES, PARAMS)

    stepper = AdaptiveStepper(field, TAB, CFG)
    state, _ = drive(stepper,
                     stepper.init_state(X0_LANES, T0, T1_LANES, lanes=B),
                     PARAMS, pause_after=3)
    sol = stepper.finalize(state)
    lam_N = jax.grad(loss)(sol.x_final)
    lam0, gtheta = symplectic_replay(
        field, TAB, (sol.xs, sol.ts, sol.hs, sol.n_accepted), PARAMS, lam_N)
    assert tree_bits_equal(g_one[0], lam0)
    assert tree_bits_equal(g_one[1], gtheta)


# ---------------------------------------------------------------------------
# fixed grid (cross-program bitwise)
# ---------------------------------------------------------------------------

N_STEPS = 8


def test_fixed_pause_resume_bit_exact():
    one_shot = rk_solve_fixed(field, TAB, X0, T0, T1, N_STEPS, PARAMS)
    stepper = FixedStepper(field, TAB, N_STEPS)
    state = stepper.init_state(X0, T0, T1)
    adv = jax.jit(stepper.advance)
    for n in range(N_STEPS):
        assert not bool(stepper.is_done(state))
        state = adv(state, PARAMS)
        if n == N_STEPS // 2:
            state = save_restore(state)
    assert bool(stepper.is_done(state))
    resumed = stepper.finalize(state)
    assert tree_bits_equal(one_shot._asdict(), resumed._asdict())


def test_fixed_pause_resume_gradients_bit_exact():
    g_one = jax.grad(
        lambda x0, p: loss(symplectic_solve(
            field, TAB, N_STEPS, "auto", x0, T0, T1, p)),
        argnums=(0, 1))(X0, PARAMS)

    stepper = FixedStepper(field, TAB, N_STEPS)
    state = stepper.init_state(X0, T0, T1)
    adv = jax.jit(stepper.advance)
    for n in range(N_STEPS):
        state = adv(state, PARAMS)
        if n == 2:
            state = save_restore(state)
    sol = stepper.finalize(state)
    lam_N = jax.grad(loss)(sol.x_final)
    lam0, gtheta = symplectic_replay(field, TAB, (sol.xs, sol.ts, sol.h),
                                     PARAMS, lam_N)
    assert tree_bits_equal(g_one[0], lam0)
    assert tree_bits_equal(g_one[1], gtheta)
