"""Explicit Runge-Kutta integration over arbitrary pytree states.

Three drivers, all thin loops over the stepper state machine in
core/stepper.py (``init_state -> advance* -> finalize``):

  * ``rk_solve_fixed``    — N equal steps: a ``FixedStepper`` run as one
                            lax.scan over ``advance`` (scan, not while_loop,
                            so DirectBackprop / remat strategies can still
                            differentiate straight through it; used by the
                            LM node_mode and all dry-run cells).
  * ``rk_solve_adaptive`` — PI-controlled adaptive stepping: an
                            ``AdaptiveStepper`` run as one lax.while_loop
                            whose carry IS the ``SolverState`` — bounded
                            ``max_steps`` checkpoint buffers (used by the
                            CNF / physics experiments, mirroring the
                            paper's dopri5-adaptive setting).
  * ``rk_solve_adaptive_batched`` — B independent trajectories, one
                            while_loop, masked per-lane control: the SAME
                            stepper with a lane-batched ``SolverState``.

All record the step checkpoints {x_n, t_n, h_n} that Algorithm 1 of the
paper retains; computation graphs are never part of the residuals (the
gradient strategies in api.py decide what autodiff sees).  Because the
between-steps state is an explicit registered pytree, any solve can also be
paused, saved, restored, and resumed bit-identically — and the
continuous-batching serve engine (repro.serve) drives the same ``advance``
over a masked lane state, inserting new trajectories mid-flight.

Stage representation: slopes are held in a *stacked* buffer — one leading
stage dimension per leaf — and every stage linear combination (stage states,
the step update, the embedded error) goes through the StageCombiner
(core/combine.py), which fuses each combination into a single HBM pass and
dispatches between the jnp oracle and the Pallas ``butcher_combine`` kernel
via the ``combine_backend`` knob.  The fixed-grid driver never computes the
embedded error estimate (there is no step controller to consume it), saving
one error combine — and, for tableaus whose error weights reference
f(x_{n+1}), one whole network evaluation — per step.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from .combine import get_combiner
from .tableau import ButcherTableau
from .stepper import (  # noqa: F401  (re-exports: the step-level surface)
    ON_FAILURE_POLICIES, AdaptiveConfig, AdaptiveSolution, AdaptiveStepper,
    BatchedAdaptiveSolution, FixedSolution, FixedSolverState, FixedStepper,
    Pytree, SolverState, VectorField, _error_norm, _error_norm_lanes,
    _time_resolution, lane_bcast, lane_count, rk_stages, rk_step)


@dataclasses.dataclass(frozen=True)
class SlicedField:
    """A vector field that reads ONE leading-axis slice of its parameters.

    ``index(t)`` is the int32 slice the field reads at time ``t`` and
    ``apply(x, t, slice_params)`` the field on that slice.  Called as
    ``f(x, t, params)`` it is the plain field: ``apply`` on
    ``params[index(t)]`` (per leaf), so every driver that calls it as a
    ``VectorField`` runs the same program as for an undeclared field.  The
    symplectic backward (core/symplectic.py) reads the declaration: each
    stage's VJP is taken with respect to the one slice, and that slice is
    added into the parameter gradient in place.
    """
    index: Callable[[jnp.ndarray], jnp.ndarray]
    apply: VectorField

    def __call__(self, x, t, params):
        return self.apply(x, t, take_slice(params, self.index(t)))


def take_slice(params: Pytree, k) -> Pytree:
    """``params[k]`` along the leading axis of every leaf."""
    return jax.tree_util.tree_map(
        lambda l: jax.lax.dynamic_index_in_dim(l, k, 0, keepdims=False),
        params)


def time_zero_cotangent(t):
    """A zero cotangent whose aval MATCHES the primal time argument.

    The drivers integrate in ``jnp.result_type(float)`` internally, but a
    custom_vjp backward pass must return cotangents in the dtype the caller
    actually passed (e.g. a float32 ``t0`` under x64) — so each fwd stows
    the primal time values in the residuals and the bwd zeros them out
    here, instead of fabricating ``result_type(float)`` zeros.
    """
    return jnp.zeros_like(jnp.asarray(t))


def time_lift(t):
    """Lift a scalar time to a ``(1,)``-shaped array for a custom_vjp driver.

    The gradient drivers' custom_vjp boundaries must not expose RANK-0
    differentiable primal inputs: ``shard_map``'s transpose rule assigns
    backward out_names from the forward in_names, and on this jax a rank-0
    cotangent paired with a non-empty name set fails the spec check
    (``_SpecError``) — so ``jax.grad`` through
    ``shard_map(solve, ...)`` dies on scalar ``t0``/``t1``.  Every driver
    therefore takes its scalar times as ``(1,)`` arrays internally (the
    public wrappers lift here, the driver reads them back via
    ``time_unlift``), which keeps the custom_vjp's cotangent avals rank-1
    and sharding-legible.  Rank-1 times — ``SaveAt.ts``, and the (B,)
    per-lane horizons the batched drivers accept — are already lifted and
    pass through untouched.
    """
    t = jnp.asarray(t)
    return jnp.reshape(t, (1,)) if t.ndim == 0 else t


def time_unlift(tr):
    """Read a ``time_lift``-ed time back inside a driver: a ``(1,)``
    lifted scalar becomes the scalar again; per-lane ``(B,)`` arrays pass
    through.  (A genuine per-lane ``(1,)`` horizon for a B=1 batch also
    reads back scalar — the drivers broadcast shared times over lanes, so
    the solve is identical.)"""
    return tr[0] if tr.shape == (1,) else tr


def tree_scale_add(base: Pytree, terms) -> Pytree:
    """base + sum_i coef_i * tree_i via chained per-leaf AXPYs.

    ``terms`` is a list of (coef, tree).  Zero coefficients (python floats)
    are dropped at trace time.  This is the UNFUSED combination path — s+2
    HBM passes; the solver hot loop uses the StageCombiner instead.  Kept as
    the reference for tests and benchmarks/bench_combine.py.
    """
    terms = [(c, t) for (c, t) in terms
             if not (isinstance(c, float) and c == 0.0)]
    if not terms:
        return base
    leaves_b, treedef = jax.tree_util.tree_flatten(base)
    term_leaves = [jax.tree_util.tree_flatten(t)[0] for _, t in terms]
    coefs = [c for c, _ in terms]
    out = []
    for idx, lb in enumerate(leaves_b):
        acc = lb
        for c, leaves in zip(coefs, term_leaves):
            acc = acc + jnp.asarray(c, dtype=lb.dtype) * leaves[idx]
        out.append(acc)
    return jax.tree_util.tree_unflatten(treedef, out)


def rk_solve_fixed(f: VectorField, tab: ButcherTableau, x0, t0, t1,
                   n_steps: int, params,
                   combine_backend: str = "auto") -> FixedSolution:
    stepper = FixedStepper(f, tab, n_steps, combine_backend)
    state = stepper.run(stepper.init_state(x0, t0, t1), params)
    return stepper.finalize(state)


# ---------------------------------------------------------------------------
# Adaptive stepping (PI controller), bounded buffer of accepted checkpoints.
# ---------------------------------------------------------------------------

def rk_solve_adaptive(f: VectorField, tab: ButcherTableau, x0, t0, t1,
                      params, cfg: AdaptiveConfig,
                      combine_backend: str = "auto",
                      h0=None) -> AdaptiveSolution:
    """PI-controlled adaptive solve on [t0, t1].

    ``h0`` (optional, traced ok) seeds the controller with a step MAGNITUDE
    — e.g. the ``h_final`` of a preceding segment in a SaveAt solve — and
    falls back to ``cfg.initial_step`` when absent or zero.  The carried
    controller step ``h`` is never clamped: each trial uses
    ``h_eff = min(|h|, |t1 - t|)`` but the controller update is based on the
    unclamped ``h`` for landing steps — an accepted clamped step keeps
    ``h``, a rejected one retries from ``h * factor`` — so a tiny final
    step against the t1 boundary cannot collapse the step size for a
    continuation (or for a backward adjoint solve reusing the config),
    whether the landing trial succeeds or not.

    The whole driver is ``AdaptiveStepper.run``: one lax.while_loop over
    ``advance``, carrying the explicit ``SolverState`` — every controller
    rule (clamp, PI factor, commit, budgets) lives in ``advance`` and is
    shared verbatim with the batched driver and the serve engine.
    """
    stepper = AdaptiveStepper(f, tab, cfg, combine_backend)
    state = stepper.init_state(x0, t0, t1, h0)
    return stepper.finalize(stepper.run(state, params))


def _raise_on_failure_cb(ok):
    if not bool(ok):
        raise RuntimeError(
            "odeint: adaptive solver exhausted max_steps/max_attempts "
            "without reaching t1 (AdaptiveConfig(on_failure='raise'))")


def apply_on_failure(x_final: Pytree, succeeded, on_failure: str) -> Pytree:
    """Apply an AdaptiveConfig.on_failure policy to a solver result.

    ``succeeded`` may be a scalar (one trajectory) or a per-lane (B,)
    vector (``batch_axis=0`` — lane axis 0 of every leaf): "nan" poisons
    exactly the failed trajectories, "raise" raises when any failed.
    """
    if on_failure == "ignore":
        return x_final
    if on_failure == "raise":
        jax.debug.callback(_raise_on_failure_cb, jnp.all(succeeded))
        return x_final
    assert on_failure == "nan", on_failure

    def poison(l):
        if not jnp.issubdtype(l.dtype, jnp.inexact):
            return l
        return jnp.where(lane_bcast(succeeded, l), l,
                         jnp.full_like(l, jnp.nan))

    return jax.tree_util.tree_map(poison, x_final)


# Named alias for the per-lane reading at batched call sites; the policy
# logic lives once in apply_on_failure (lane_bcast handles both ranks).
apply_on_failure_lanes = apply_on_failure


# ---------------------------------------------------------------------------
# Batch-native adaptive stepping: one while_loop, masked per-lane control.
# ---------------------------------------------------------------------------

def rk_solve_adaptive_batched(f: VectorField, tab: ButcherTableau, x0,
                              t0, t1, params, cfg: AdaptiveConfig,
                              combine_backend: str = "auto",
                              h0=None) -> BatchedAdaptiveSolution:
    """Adaptive solve of B independent trajectories in ONE while_loop.

    ``x0`` is lane-batched (lane axis 0 of every leaf).  Each lane carries
    its own ``(t, h, n_accepted, n_attempts)`` controller state, its own
    error norm (``_error_norm_lanes``: the single-trajectory norm per lane,
    never pooled across the batch), and its own accept/reject decision —
    finished and rejected lanes are masked on commit, so no lane's
    stiffness can perturb another lane's accepted grid.  The loop runs
    until every lane lands (or exhausts its budgets), and each trial step
    evaluates ``f`` ONCE over the full batch (the stage combines stay fused
    through the StageCombiner under ``vmap``), so the hot path keeps its
    batched shape; iterations where some lanes are already done spend
    wasted lane-slots, which is the price of the fused evaluation
    (docs/batching.md quantifies the trade against lockstep batch-in-state
    solving).

    Every controller rule matches ``rk_solve_adaptive`` per lane — the
    unclamped-h carry for landing steps, the dtype-aware termination
    threshold, the PI factor — because it IS the same rule: both drivers
    run ``AdaptiveStepper.advance``, whose state is scalar () for a single
    trajectory and (B,) here — so lane b of the result is the
    single-trajectory solve of lane b to rounding (tests/test_batch.py).
    ``t0``/``t1``/``h0`` may be scalars (shared) or (B,) per-lane arrays.
    """
    B = lane_count(x0)
    stepper = AdaptiveStepper(f, tab, cfg, combine_backend)
    state = stepper.init_state(x0, t0, t1, h0, lanes=B)
    return stepper.finalize(stepper.run(state, params))


def rk_solve_adaptive_batched_saveat_stacked(
        f: VectorField, tab: ButcherTableau, x0, t0, ts: jnp.ndarray,
        params, cfg: AdaptiveConfig, combine_backend: str = "auto"):
    """Batched analogue of ``rk_solve_adaptive_saveat_stacked``: one scanned
    segment chain, per-lane controller state ``(x, h_final)`` threading
    across every observation boundary (each lane's landing step stays
    unclamped in ITS carry).  Observation times are shared across lanes.
    A lane whose segment fails is poisoned per ``cfg.on_failure`` without
    touching its batchmates, and the poison propagates to that lane's later
    observations.  Returns (obs, sols) with a leading len(ts) segment axis
    on every ``BatchedAdaptiveSolution`` field.
    """
    dtype = jnp.result_type(float)
    ts = jnp.asarray(ts, dtype)
    B = lane_count(x0)
    t_starts = segment_starts(t0, ts)

    def body(carry, seg):
        x, h = carry
        a, b = seg
        sol = rk_solve_adaptive_batched(f, tab, x, a, b, params, cfg,
                                        combine_backend, h0=h)
        x = apply_on_failure_lanes(sol.x_final, sol.succeeded,
                                   cfg.on_failure)
        sol = sol._replace(x_final=x)
        return (x, sol.h_final), sol

    _, sols = jax.lax.scan(body, (x0, jnp.zeros((B,), dtype)),
                           (t_starts, ts))
    return sols.x_final, sols


# ---------------------------------------------------------------------------
# SaveAt support: segmented adaptive solves + Hermite dense output.
# ---------------------------------------------------------------------------

def segment_starts(t0, ts: jnp.ndarray) -> jnp.ndarray:
    """Left endpoints of the observation segments: [t0, ts[0], ..., ts[-2]].

    Zipped with ``ts`` these are the (start, end) pairs every scanned
    SaveAt driver iterates over.
    """
    t0 = jnp.reshape(jnp.asarray(t0, ts.dtype), (1,))
    return jnp.concatenate([t0, ts[:-1]])


def rk_solve_adaptive_saveat_stacked(f: VectorField, tab: ButcherTableau,
                                     x0, t0, ts: jnp.ndarray, params,
                                     cfg: AdaptiveConfig,
                                     combine_backend: str = "auto"):
    """Adaptive solve observed at the times ``ts`` by segmenting the solve.

    One adaptive sub-solve per segment [t0, ts[0]], [ts[0], ts[1]], ...; the
    controller state threads across segments (each segment seeds its step
    from the previous segment's unclamped ``h_final``, so landing exactly on
    an observation time costs one clamped step, not a collapsed restart).
    A failed segment poisons its state per ``cfg.on_failure`` and the
    poison propagates to every later observation.

    The segments run inside ONE ``lax.scan`` (every segment shares the
    ``max_steps`` buffer bound, so shapes are uniform): trace size, jaxpr
    size, and compile time are O(1) in len(ts).

    Returns (obs, sols): ``obs`` the stacked observations (leading dim
    len(ts)), ``sols`` an AdaptiveSolution whose every field carries a
    leading len(ts) segment axis.
    """
    dtype = jnp.result_type(float)
    ts = jnp.asarray(ts, dtype)
    t_starts = segment_starts(t0, ts)

    def body(carry, seg):
        x, h = carry
        a, b = seg
        sol = rk_solve_adaptive(f, tab, x, a, b, params, cfg,
                                combine_backend, h0=h)
        x = apply_on_failure(sol.x_final, sol.succeeded, cfg.on_failure)
        sol = sol._replace(x_final=x)
        return (x, sol.h_final), sol

    # h0 = 0 makes the first segment fall back to cfg.initial_step.
    _, sols = jax.lax.scan(body, (x0, jnp.zeros((), dtype)),
                           (t_starts, ts))
    return sols.x_final, sols


def rk_solve_adaptive_saveat(f: VectorField, tab: ButcherTableau, x0, t0,
                             ts: jnp.ndarray, params, cfg: AdaptiveConfig,
                             combine_backend: str = "auto"):
    """List-of-segments convenience wrapper around the scanned driver.

    Returns (obs, sols) with ``sols`` a Python list of per-segment
    AdaptiveSolutions (unstacked views into the scanned buffers).  Solver
    hot paths use ``rk_solve_adaptive_saveat_stacked`` directly — the
    unstacking here costs O(len(ts)) trace equations and is meant for
    inspection and tests.
    """
    obs, stacked = rk_solve_adaptive_saveat_stacked(
        f, tab, x0, t0, ts, params, cfg, combine_backend)
    sols = [jax.tree_util.tree_map(lambda l: l[i], stacked)
            for i in range(ts.shape[0])]
    return obs, sols


def hermite_observe(f: VectorField, tab: ButcherTableau,
                    sol: AdaptiveSolution, params, taus: jnp.ndarray,
                    combine_backend: str = "auto") -> Pytree:
    """Dense-output observation of ONE adaptive solve at the times ``taus``.

    4th-order cubic-Hermite interpolation over the accepted step containing
    each tau (StageCombiner.interpolate — the same row-combine primitive as
    the Butcher rows).  The step endpoints come from the checkpoint buffer;
    their slopes are recomputed (2 extra f-evals per observation), so the
    step controller is never perturbed by observation times.  taus outside
    the integrated span clamp to the nearest endpoint.
    """
    combiner = get_combiner(tab, combine_backend)
    max_steps = sol.ts.shape[0]
    n_acc = sol.n_accepted
    last = jnp.maximum(n_acc - 1, 0)
    direction = jnp.sign(jnp.where(n_acc > 0, sol.hs[0], 1.0))
    valid = jnp.arange(max_steps) < n_acc
    keys = jnp.where(valid, direction * sol.ts, jnp.inf)

    def observe_one(tau):
        n = jnp.clip(jnp.searchsorted(keys, direction * tau,
                                      side="right") - 1, 0, last)
        t_n = sol.ts[n]
        h_n = sol.hs[n]
        x_n = jax.tree_util.tree_map(
            lambda b: jax.lax.dynamic_index_in_dim(b, n, 0, keepdims=False),
            sol.xs)
        # x_{n+1}: next checkpoint, or x_final for the last accepted step.
        is_last = n >= n_acc - 1
        x_n1 = jax.tree_util.tree_map(
            lambda b, xf: jnp.where(
                is_last, xf,
                jax.lax.dynamic_index_in_dim(
                    b, jnp.minimum(n + 1, max_steps - 1), 0,
                    keepdims=False)),
            sol.xs, sol.x_final)
        theta = jnp.clip((tau - t_n) / jnp.where(h_n == 0, 1.0, h_n),
                         0.0, 1.0)
        f0 = f(x_n, t_n, params)
        f1 = f(x_n1, t_n + h_n, params)
        out = combiner.interpolate(x_n, x_n1, f0, f1, h_n, theta)
        # degenerate solve (no accepted steps): the state never moved.
        return jax.tree_util.tree_map(
            lambda o, xf: jnp.where(n_acc > 0, o, xf), out, sol.x_final)

    # observe_one is elementwise in tau: ONE traced copy serves every
    # observation (and slope recomputations batch), instead of unrolling
    # the search + interpolate + 2-f-eval graph per tau.
    return jax.vmap(observe_one)(taus)
