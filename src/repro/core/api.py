"""Composable solve API: solver x gradient x stepping x observation.

The paper's contribution is a *gradient strategy* — the symplectic adjoint —
that composes orthogonally with the solver tableau, the step controller, and
the observation scheme.  This module makes each axis a first-class object and
gives them a single entry point:

    sol = solve(f, x0, params,
                saveat=SaveAt(ts=jnp.linspace(0.1, 1.0, 64)),
                method="dopri5",
                gradient=SymplecticAdjoint(),
                stepping=AdaptiveConfig(rtol=1e-6, atol=1e-8))
    sol.ys           # observations (stacked over SaveAt.ts) or final state
    sol.stats        # n_steps / n_fevals / n_attempts (non-differentiated)
    sol.success      # bool: adaptive budgets were sufficient
    sol.final_state  # the state at the end of integration

``Solution`` is a registered pytree, so the one call shape works unchanged
under ``jit``, ``vmap`` (batched ``x0``), and ``grad`` (losses on ``sol.ys``;
stats ride along as integer auxiliaries that autodiff never touches, and XLA
dead-code-eliminates their computation under ``jit`` when they go unused).
Strategies whose drivers expose the controller counters serve value and
stats from one run; for the custom-VJP strategies the adaptive stats come
from a stop_gradient controller replay — free under ``jit`` (CSE/DCE), a
real second integration in eager adaptive solves (docs/api.md, Cost note).

Gradient strategies are frozen dataclasses carrying their own knobs:

    SymplecticAdjoint()                  — the paper: exact gradient,
                                           memory O(N + s + L)    [default]
    DirectBackprop()                     — differentiate through the solver:
                                           exact gradient, memory O(N s L)
    RematStep()                          — ANODE/ACA step checkpointing:
                                           exact gradient, memory O(N + s L)
    RematSolve()                         — whole-solve rematerialization:
                                           exact, memory O(N s L) in bwd
    ContinuousAdjoint(steps_multiplier=...,
                      bwd_adaptive=...)  — Chen et al. 2018: approximate
                                           gradient, memory O(L)

Each strategy registers itself in ``GRADIENT_REGISTRY`` under a short name
(``register_gradient``); a sixth scheme is one subclass away — ``solve`` never
grows another ``elif`` (tests/test_api.py registers a toy strategy to prove
it).  Which (stepping, saveat) cells a strategy supports is declared on the
class as a ``capabilities`` frozenset; ``capability_matrix()`` assembles the
full declarative table (rendered in docs/api.md) and every illegal combination
fails with the same uniformly-shaped ``ValueError``.

``SaveAt`` chooses the observation scheme: ``SaveAt(t1=...)`` returns the
final state; ``SaveAt(ts=...)`` observes at each time in ``ts`` by
checkpointed segmentation (exact discrete gradients, any strategy that
supports it); ``SaveAt(ts=..., dense=True)`` runs ONE unsegmented adaptive
solve and interpolates with 4th-order Hermite dense output (the controller
never sees the observation times; DirectBackprop only).  ``ts`` must be
monotone in the direction of integration — duplicates are allowed
(zero-length segments), and concrete non-monotone arrays are rejected
eagerly at trace time.

``stepping`` is either an ``int`` (fixed grid, N equal steps — per segment
when observing) or an ``AdaptiveConfig`` (PI-controlled adaptive stepping,
``max_steps`` per segment).

``batch_axis=0`` declares the leading axis of every state leaf a batch of
INDEPENDENT trajectories: adaptive solves then run masked per-lane step
control (each lane its own error norm, accept/reject, and accepted grid —
no cross-lane coupling) in one fused while_loop, ``stats``/``success``
become per-lane (B,) arrays, and the symplectic/continuous adjoints replay
each lane's own grid, so batched gradients match a loop of single solves
to rounding (docs/batching.md; ``batched_capability_matrix()`` declares
which cells support it).

The legacy ``odeint`` / ``odeint_with_stats`` front-ends survive as thin
deprecation shims over ``solve`` (core/odeint.py); docs/api.md carries the
old-kwarg -> new-object migration table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, FrozenSet, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import scopes
from .adjoint import (odeint_adjoint, odeint_adjoint_adaptive,
                      odeint_adjoint_adaptive_batched)
from .backprop import odeint_backprop, odeint_remat_solve, odeint_remat_step
from .combine import resolve_backend
from .rk import (AdaptiveConfig, VectorField, apply_on_failure,
                 apply_on_failure_lanes, hermite_observe, lane_count,
                 rk_solve_adaptive, rk_solve_adaptive_batched,
                 rk_solve_adaptive_batched_saveat_stacked,
                 rk_solve_adaptive_saveat_stacked, rk_solve_fixed,
                 segment_starts)
from .symplectic import (odeint_symplectic, odeint_symplectic_adaptive,
                         odeint_symplectic_adaptive_batched,
                         odeint_symplectic_saveat,
                         odeint_symplectic_saveat_adaptive,
                         odeint_symplectic_saveat_adaptive_batched)
from .tableau import ButcherTableau, get_tableau

Pytree = Any

STEPPING_KINDS = ("fixed", "adaptive")
SAVEAT_KINDS = ("t1", "ts", "dense")


# ---------------------------------------------------------------------------
# SaveAt: what to observe
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SaveAt:
    """Observation scheme: exactly one of ``t1`` (final state) or ``ts``
    (stacked observations; the solve ends at ``ts[-1]``).

    ``dense=True`` selects Hermite dense-output interpolation at ``ts``
    instead of checkpointed segmentation (adaptive solves only; the step
    controller never sees the observation times)."""
    t1: Optional[Any] = None
    ts: Optional[Any] = None
    dense: bool = False

    def __post_init__(self):
        if self.t1 is not None and self.ts is not None:
            raise ValueError(
                "pass EITHER t1 or ts: with observation times the solve "
                "ends at ts[-1] (include the end time in ts)")
        if self.t1 is None and self.ts is None:
            raise ValueError("SaveAt needs one of t1=... or ts=...")
        if self.dense and self.ts is None:
            raise ValueError("SaveAt(dense=True) needs observation times "
                             "ts=..., not t1")

    @property
    def kind(self) -> str:
        if self.ts is None:
            return "t1"
        return "dense" if self.dense else "ts"


def _as_ts(ts, dtype, t0=None) -> jnp.ndarray:
    """Validate and coerce observation times.

    Enforces the documented monotonicity contract eagerly wherever the
    values are concrete (trace-time check; tracers — e.g. under vmap over
    ts — are passed through).  Duplicates are legal zero-length segments;
    descending ts is legal reverse-time integration, but the direction must
    be consistent across [t0, ts[0], ..., ts[-1]]."""
    ts = jnp.asarray(ts, dtype=dtype)
    if ts.ndim != 1 or ts.shape[0] == 0:
        raise ValueError("ts must be a non-empty 1-D array of observation "
                         f"times; got shape {ts.shape}")
    if not isinstance(ts, jax.core.Tracer):
        seq = np.asarray(ts)
        if t0 is not None and not isinstance(t0, jax.core.Tracer):
            seq = np.concatenate([np.reshape(np.asarray(t0), (1,)), seq])
        d = np.diff(seq)
        if not (np.all(d >= 0) or np.all(d <= 0)):
            raise ValueError(
                "ts must be monotone in the direction of integration "
                "(duplicates are allowed; descending ts is reverse-time); "
                f"got t0={None if t0 is None else np.asarray(t0)} "
                f"ts={np.asarray(ts)}")
    return ts


# ---------------------------------------------------------------------------
# Solution: the one return shape
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Solution:
    """Result of ``solve``: a registered pytree.

    ys          — the observed solution: stacked over ``SaveAt.ts`` (leading
                  axis len(ts) per leaf) or the final state for ``SaveAt.t1``.
                  Differentiable under the selected gradient strategy.
    final_state — the state at the end of integration (== ``ys`` for t1;
                  the last observation for ts).
    stats       — {"n_steps", "n_fevals", "n_attempts"}: int32 counters of
                  the realized solve.  Exact static counts on fixed grids;
                  the controller's realized counters on adaptive solves.
                  Scalars for a single trajectory; per-lane (B,) arrays
                  under ``solve(..., batch_axis=0)``.  Never
                  differentiated; dead-code-eliminated under jit when
                  unused.
    success     — bool: the solve reached its target time within the
                  adaptive budgets (always True on fixed grids).  Per-lane
                  (B,) under ``batch_axis=0`` — one stiff lane failing
                  does not flag (or poison) its batchmates.
    """
    ys: Pytree
    final_state: Pytree
    stats: Dict[str, jnp.ndarray]
    success: jnp.ndarray

    def tree_flatten(self):
        return ((self.ys, self.final_state, self.stats, self.success), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


# ---------------------------------------------------------------------------
# Gradient strategies
# ---------------------------------------------------------------------------

class _Ctx:
    """Static per-solve context handed to every strategy hook."""
    __slots__ = ("f", "tab", "n_steps", "adaptive", "backend")

    def __init__(self, f: VectorField, tab: ButcherTableau,
                 n_steps: Optional[int], adaptive: Optional[AdaptiveConfig],
                 backend: str):
        self.f = f
        self.tab = tab
        self.n_steps = n_steps
        self.adaptive = adaptive
        self.backend = backend


def _segmented(solve_one: Callable, x0, t0, ts):
    """Generic SaveAt segmentation: chain per-segment solves inside ONE
    lax.scan, stacking the segment endpoints.  Observation cotangents are
    injected at the boundaries automatically by reverse-mode through the
    composition; trace/jaxpr size is O(1) in len(ts) (docs/adaptive.md)."""
    def body(x, seg):
        a, b = seg
        x = solve_one(x, a, b)
        return x, x

    _, obs = jax.lax.scan(body, x0, (segment_starts(t0, ts), ts))
    return obs


_FIXED_T1 = ("fixed", "t1")
_FIXED_TS = ("fixed", "ts")
_ADAPT_T1 = ("adaptive", "t1")
_ADAPT_TS = ("adaptive", "ts")
_ADAPT_DENSE = ("adaptive", "dense")


class GradientStrategy:
    """Base class for gradient strategies.

    A strategy declares its legal (stepping, saveat) cells in
    ``capabilities`` and implements the value hooks for the cells it
    supports; the SaveAt hooks default to generic checkpointed segmentation
    over the plain solves, and the stats hooks default to a non-
    differentiated controller replay — so a minimal new strategy is
    ``name`` + ``capabilities`` + ``fixed`` (and ``adaptive`` if claimed).
    Register it with ``@register_gradient``; ``solve`` needs no edits.
    """
    name: ClassVar[str]
    capabilities: ClassVar[FrozenSet[Tuple[str, str]]]
    # adaptive cells ALSO legal under ``solve(..., batch_axis=0)`` — i.e.
    # cells for which the strategy has a masked per-lane batched driver.
    # Fixed-grid cells never appear here: a fixed grid is state-independent,
    # so every claimed fixed cell is batchable for free (``batched_cells``).
    batched_capabilities: ClassVar[FrozenSet[Tuple[str, str]]] = frozenset()

    @classmethod
    def batched_cells(cls) -> FrozenSet[Tuple[str, str]]:
        """(stepping, saveat) cells legal with ``batch_axis=0``: every fixed
        cell the strategy claims (the grid cannot depend on the state, so
        batch-in-state already IS per-lane exact) plus the declared
        ``batched_capabilities`` adaptive cells."""
        fixed = frozenset(c for c in cls.capabilities if c[0] == "fixed")
        return fixed | cls.batched_capabilities

    # -- value hooks --------------------------------------------------------
    def fixed(self, ctx: _Ctx, x0, t0, t1, params):
        raise NotImplementedError

    def adaptive(self, ctx: _Ctx, x0, t0, t1, params):
        raise NotImplementedError

    def fixed_saveat(self, ctx: _Ctx, x0, t0, ts, params):
        return _segmented(lambda x, a, b: self.fixed(ctx, x, a, b, params),
                          x0, t0, ts)

    def adaptive_saveat(self, ctx: _Ctx, x0, t0, ts, params):
        return _segmented(
            lambda x, a, b: self.adaptive(ctx, x, a, b, params), x0, t0, ts)

    # -- stats hooks (non-differentiated controller replays) ----------------
    def adaptive_stats(self, ctx: _Ctx, x0, t0, t1, params):
        """Counters of the realized adaptive solve.  Default: replay the
        controller once under stop_gradient with the exact arguments every
        driver's forward pass uses — the counters match the value solve
        bit-for-bit, and under jit XLA CSE/DCE collapses the duplicate."""
        sol = rk_solve_adaptive(ctx.f, ctx.tab, jax.lax.stop_gradient(x0),
                                t0, t1, jax.lax.stop_gradient(params),
                                ctx.adaptive, ctx.backend)
        return ({"n_steps": sol.n_accepted, "n_fevals": sol.n_fevals,
                 "n_attempts": sol.n_attempts}, sol.succeeded)

    def adaptive_saveat_stats(self, ctx: _Ctx, x0, t0, ts, params):
        """Default segmented replay RESTARTS the controller at every
        observation boundary — exactly the step sequence the default
        ``adaptive_saveat`` (generic segmentation over ``adaptive``)
        realizes.  Strategies whose SaveAt drivers thread the controller
        step across boundaries (symplectic, backprop) override this with
        the threaded stacked replay so stats and value always describe the
        SAME solve."""
        cfg = ctx.adaptive
        x0 = jax.lax.stop_gradient(x0)
        params = jax.lax.stop_gradient(params)

        def body(x, seg):
            a, b = seg
            sol = rk_solve_adaptive(ctx.f, ctx.tab, x, a, b, params, cfg,
                                    ctx.backend)
            x = apply_on_failure(sol.x_final, sol.succeeded, cfg.on_failure)
            return x, (sol.n_accepted, sol.n_fevals, sol.n_attempts,
                       sol.succeeded)

        _, (na, nf, nt, ok) = jax.lax.scan(body, x0,
                                           (segment_starts(t0, ts), ts))
        return ({"n_steps": jnp.sum(na), "n_fevals": jnp.sum(nf),
                 "n_attempts": jnp.sum(nt)}, jnp.all(ok))

    # -- combined value+stats hooks (what ``solve`` actually calls) ---------
    def adaptive_with_stats(self, ctx: _Ctx, x0, t0, t1, params):
        """Value + stats for an adaptive t1 solve.  Strategies whose value
        driver already exposes the controller counters override this to a
        single run (DirectBackprop); custom-VJP strategies keep the
        default value-hook + replay pair."""
        ys = self.adaptive(ctx, x0, t0, t1, params)
        stats, success = self.adaptive_stats(ctx, x0, t0, t1, params)
        return ys, stats, success

    def adaptive_saveat_with_stats(self, ctx: _Ctx, x0, t0, ts, params):
        ys = self.adaptive_saveat(ctx, x0, t0, ts, params)
        stats, success = self.adaptive_saveat_stats(ctx, x0, t0, ts, params)
        return ys, stats, success

    def dense_saveat_with_stats(self, ctx: _Ctx, x0, t0, ts, params):
        """Dense-output observation.  NOTE: unlike the plain value hooks
        this returns the (ys, stats, success) triple — dense output and
        its controller run are inseparable, so there is no value-only
        form.  Unreachable unless the strategy claims ('adaptive',
        'dense')."""
        raise NotImplementedError

    # -- batched hooks (masked per-lane adaptive control, batch_axis=0) -----
    # Stats and success are PER LANE: (B,) int32 / bool arrays.
    def adaptive_batched(self, ctx: _Ctx, x0, t0, t1, params):
        raise NotImplementedError

    def adaptive_saveat_batched(self, ctx: _Ctx, x0, t0, ts, params):
        return _segmented(
            lambda x, a, b: self.adaptive_batched(ctx, x, a, b, params),
            x0, t0, ts)

    def adaptive_batched_stats(self, ctx: _Ctx, x0, t0, t1, params):
        """Per-lane counters of the realized batched solve (stop_gradient
        controller replay, exactly like ``adaptive_stats``)."""
        sol = rk_solve_adaptive_batched(
            ctx.f, ctx.tab, jax.lax.stop_gradient(x0), t0, t1,
            jax.lax.stop_gradient(params), ctx.adaptive, ctx.backend)
        return ({"n_steps": sol.n_accepted, "n_fevals": sol.n_fevals,
                 "n_attempts": sol.n_attempts}, sol.succeeded)

    def adaptive_saveat_batched_stats(self, ctx: _Ctx, x0, t0, ts, params):
        """Restart-per-segment batched replay, matching the step sequence
        the default ``adaptive_saveat_batched`` (generic segmentation over
        ``adaptive_batched``) realizes.  Strategies whose batched SaveAt
        drivers thread the per-lane controller step across boundaries
        override with the threaded stacked replay."""
        cfg = ctx.adaptive
        x0 = jax.lax.stop_gradient(x0)
        params = jax.lax.stop_gradient(params)

        def body(x, seg):
            a, b = seg
            sol = rk_solve_adaptive_batched(ctx.f, ctx.tab, x, a, b, params,
                                            cfg, ctx.backend)
            x = apply_on_failure_lanes(sol.x_final, sol.succeeded,
                                       cfg.on_failure)
            return x, (sol.n_accepted, sol.n_fevals, sol.n_attempts,
                       sol.succeeded)

        _, (na, nf, nt, ok) = jax.lax.scan(body, x0,
                                           (segment_starts(t0, ts), ts))
        return ({"n_steps": jnp.sum(na, axis=0),
                 "n_fevals": jnp.sum(nf, axis=0),
                 "n_attempts": jnp.sum(nt, axis=0)}, jnp.all(ok, axis=0))

    def adaptive_batched_with_stats(self, ctx: _Ctx, x0, t0, t1, params):
        ys = self.adaptive_batched(ctx, x0, t0, t1, params)
        stats, success = self.adaptive_batched_stats(ctx, x0, t0, t1, params)
        return ys, stats, success

    def adaptive_saveat_batched_with_stats(self, ctx: _Ctx, x0, t0, ts,
                                           params):
        ys = self.adaptive_saveat_batched(ctx, x0, t0, ts, params)
        stats, success = self.adaptive_saveat_batched_stats(
            ctx, x0, t0, ts, params)
        return ys, stats, success


def _threaded_saveat_batched_stats(ctx: _Ctx, x0, t0, ts, params):
    """Per-lane stats replay for batched SaveAt drivers that thread each
    lane's controller step across observation boundaries."""
    _, sols = rk_solve_adaptive_batched_saveat_stacked(
        ctx.f, ctx.tab, jax.lax.stop_gradient(x0), t0, ts,
        jax.lax.stop_gradient(params), ctx.adaptive, ctx.backend)
    return ({"n_steps": jnp.sum(sols.n_accepted, axis=0),
             "n_fevals": jnp.sum(sols.n_fevals, axis=0),
             "n_attempts": jnp.sum(sols.n_attempts, axis=0)},
            jnp.all(sols.succeeded, axis=0))


def _threaded_saveat_stats(ctx: _Ctx, x0, t0, ts, params):
    """Stats replay for SaveAt drivers that THREAD the controller step
    across observation boundaries (the stacked-scan segmentation the
    symplectic and backprop drivers use)."""
    _, sols = rk_solve_adaptive_saveat_stacked(
        ctx.f, ctx.tab, jax.lax.stop_gradient(x0), t0, ts,
        jax.lax.stop_gradient(params), ctx.adaptive, ctx.backend)
    return ({"n_steps": jnp.sum(sols.n_accepted),
             "n_fevals": jnp.sum(sols.n_fevals),
             "n_attempts": jnp.sum(sols.n_attempts)},
            jnp.all(sols.succeeded))


GRADIENT_REGISTRY: Dict[str, Type[GradientStrategy]] = {}


def register_gradient(cls: Type[GradientStrategy]) -> Type[GradientStrategy]:
    """Class decorator: register a strategy under ``cls.name``.

    ``as_gradient(name)`` then resolves the name to a default-constructed
    instance; ``solve`` dispatches purely through the strategy interface,
    so registration is the ONLY integration point a new scheme needs."""
    GRADIENT_REGISTRY[cls.name] = cls
    return cls


def as_gradient(spec: Union[str, GradientStrategy,
                            Type[GradientStrategy]]) -> GradientStrategy:
    """Coerce a strategy instance / class / registered name to an instance."""
    if isinstance(spec, GradientStrategy):
        return spec
    if isinstance(spec, type) and issubclass(spec, GradientStrategy):
        return spec()
    if isinstance(spec, str):
        if spec not in GRADIENT_REGISTRY:
            raise ValueError(
                f"unknown gradient strategy {spec!r}; registered strategies: "
                f"{sorted(GRADIENT_REGISTRY)}")
        return GRADIENT_REGISTRY[spec]()
    raise TypeError(
        "gradient must be a GradientStrategy instance, a GradientStrategy "
        f"subclass, or a registered name; got {type(spec).__name__}")


@register_gradient
@dataclasses.dataclass(frozen=True)
class SymplecticAdjoint(GradientStrategy):
    """The paper's method: exact gradient of the discrete forward map with
    O(N + s + L) memory (Algorithm 2 backward from per-step checkpoints)."""
    name: ClassVar[str] = "symplectic"
    capabilities: ClassVar[FrozenSet] = frozenset(
        {_FIXED_T1, _FIXED_TS, _ADAPT_T1, _ADAPT_TS})
    batched_capabilities: ClassVar[FrozenSet] = frozenset(
        {_ADAPT_T1, _ADAPT_TS})

    def fixed(self, ctx, x0, t0, t1, params):
        return odeint_symplectic(ctx.f, ctx.tab, ctx.n_steps, ctx.backend,
                                 x0, t0, t1, params)

    def adaptive(self, ctx, x0, t0, t1, params):
        return odeint_symplectic_adaptive(ctx.f, ctx.tab, ctx.adaptive,
                                          ctx.backend, x0, t0, t1, params)

    def fixed_saveat(self, ctx, x0, t0, ts, params):
        return odeint_symplectic_saveat(ctx.f, ctx.tab, ctx.n_steps,
                                        ctx.backend, x0, t0, ts, params)

    def adaptive_saveat(self, ctx, x0, t0, ts, params):
        return odeint_symplectic_saveat_adaptive(
            ctx.f, ctx.tab, ctx.adaptive, ctx.backend, x0, t0, ts, params)

    def adaptive_saveat_stats(self, ctx, x0, t0, ts, params):
        return _threaded_saveat_stats(ctx, x0, t0, ts, params)

    # batched: exact per-lane gradients replaying each lane's own grid
    def adaptive_batched(self, ctx, x0, t0, t1, params):
        return odeint_symplectic_adaptive_batched(
            ctx.f, ctx.tab, ctx.adaptive, ctx.backend, x0, t0, t1, params)

    def adaptive_saveat_batched(self, ctx, x0, t0, ts, params):
        return odeint_symplectic_saveat_adaptive_batched(
            ctx.f, ctx.tab, ctx.adaptive, ctx.backend, x0, t0, ts, params)

    def adaptive_saveat_batched_stats(self, ctx, x0, t0, ts, params):
        return _threaded_saveat_batched_stats(ctx, x0, t0, ts, params)


@register_gradient
@dataclasses.dataclass(frozen=True)
class DirectBackprop(GradientStrategy):
    """Differentiate through the solver (exact; memory O(N s L)).  Adaptive
    solves are forward-value/JVP only (reverse-mode cannot cross the
    lax.while_loop); the only strategy supporting dense output."""
    name: ClassVar[str] = "backprop"
    capabilities: ClassVar[FrozenSet] = frozenset(
        {_FIXED_T1, _FIXED_TS, _ADAPT_T1, _ADAPT_TS, _ADAPT_DENSE})
    batched_capabilities: ClassVar[FrozenSet] = frozenset(
        {_ADAPT_T1, _ADAPT_TS})

    def fixed(self, ctx, x0, t0, t1, params):
        return odeint_backprop(ctx.f, ctx.tab, ctx.n_steps, x0, t0, t1,
                               params, ctx.backend)

    def adaptive(self, ctx, x0, t0, t1, params):
        sol = rk_solve_adaptive(ctx.f, ctx.tab, x0, t0, t1, params,
                                ctx.adaptive, ctx.backend)
        return apply_on_failure(sol.x_final, sol.succeeded,
                                ctx.adaptive.on_failure)

    def adaptive_saveat(self, ctx, x0, t0, ts, params):
        obs, _ = rk_solve_adaptive_saveat_stacked(
            ctx.f, ctx.tab, x0, t0, ts, params, ctx.adaptive, ctx.backend)
        return obs

    # the value drivers above ARE the controller, so value and stats come
    # from ONE run — no replay (this is also what keeps the
    # odeint_with_stats shim at its historical single-solve cost).
    def adaptive_with_stats(self, ctx, x0, t0, t1, params):
        sol = rk_solve_adaptive(ctx.f, ctx.tab, x0, t0, t1, params,
                                ctx.adaptive, ctx.backend)
        ys = apply_on_failure(sol.x_final, sol.succeeded,
                              ctx.adaptive.on_failure)
        return ys, {"n_steps": sol.n_accepted, "n_fevals": sol.n_fevals,
                    "n_attempts": sol.n_attempts}, sol.succeeded

    def adaptive_saveat_with_stats(self, ctx, x0, t0, ts, params):
        obs, sols = rk_solve_adaptive_saveat_stacked(
            ctx.f, ctx.tab, x0, t0, ts, params, ctx.adaptive, ctx.backend)
        return obs, {"n_steps": jnp.sum(sols.n_accepted),
                     "n_fevals": jnp.sum(sols.n_fevals),
                     "n_attempts": jnp.sum(sols.n_attempts)}, \
            jnp.all(sols.succeeded)

    # solve() takes the single-run combined hook above; this override
    # exists so the standalone stats hook ALSO describes the threaded
    # sequence this strategy's adaptive_saveat realizes (the base default
    # replays a restarting segmentation), keeping the hook family
    # self-consistent for subclassers and direct callers.
    def adaptive_saveat_stats(self, ctx, x0, t0, ts, params):
        return _threaded_saveat_stats(ctx, x0, t0, ts, params)

    # batched: the value drivers ARE the per-lane controllers — one run.
    def adaptive_batched(self, ctx, x0, t0, t1, params):
        sol = rk_solve_adaptive_batched(ctx.f, ctx.tab, x0, t0, t1, params,
                                        ctx.adaptive, ctx.backend)
        return apply_on_failure_lanes(sol.x_final, sol.succeeded,
                                      ctx.adaptive.on_failure)

    def adaptive_batched_with_stats(self, ctx, x0, t0, t1, params):
        sol = rk_solve_adaptive_batched(ctx.f, ctx.tab, x0, t0, t1, params,
                                        ctx.adaptive, ctx.backend)
        ys = apply_on_failure_lanes(sol.x_final, sol.succeeded,
                                    ctx.adaptive.on_failure)
        return ys, {"n_steps": sol.n_accepted, "n_fevals": sol.n_fevals,
                    "n_attempts": sol.n_attempts}, sol.succeeded

    def adaptive_saveat_batched(self, ctx, x0, t0, ts, params):
        obs, _ = rk_solve_adaptive_batched_saveat_stacked(
            ctx.f, ctx.tab, x0, t0, ts, params, ctx.adaptive, ctx.backend)
        return obs

    def adaptive_saveat_batched_with_stats(self, ctx, x0, t0, ts, params):
        obs, sols = rk_solve_adaptive_batched_saveat_stacked(
            ctx.f, ctx.tab, x0, t0, ts, params, ctx.adaptive, ctx.backend)
        return obs, {"n_steps": jnp.sum(sols.n_accepted, axis=0),
                     "n_fevals": jnp.sum(sols.n_fevals, axis=0),
                     "n_attempts": jnp.sum(sols.n_attempts, axis=0)}, \
            jnp.all(sols.succeeded, axis=0)

    def adaptive_saveat_batched_stats(self, ctx, x0, t0, ts, params):
        return _threaded_saveat_batched_stats(ctx, x0, t0, ts, params)

    def dense_saveat_with_stats(self, ctx, x0, t0, ts, params):
        # ONE unsegmented solve + Hermite interpolation: value and stats
        # come from the same controller run (2 extra f-evals per
        # observation for the endpoint slopes).
        cfg = ctx.adaptive
        sol = rk_solve_adaptive(ctx.f, ctx.tab, x0, t0, ts[-1], params,
                                cfg, ctx.backend)
        obs = hermite_observe(ctx.f, ctx.tab, sol, params, ts, ctx.backend)
        ys = apply_on_failure(obs, sol.succeeded, cfg.on_failure)
        stats = {"n_steps": sol.n_accepted,
                 "n_fevals": sol.n_fevals + 2 * ts.shape[0],
                 "n_attempts": sol.n_attempts}
        return ys, stats, sol.succeeded


@register_gradient
@dataclasses.dataclass(frozen=True)
class RematStep(GradientStrategy):
    """ANODE/ACA-style per-step rematerialization (exact; O(N + s L))."""
    name: ClassVar[str] = "remat_step"
    capabilities: ClassVar[FrozenSet] = frozenset({_FIXED_T1, _FIXED_TS})

    def fixed(self, ctx, x0, t0, t1, params):
        return odeint_remat_step(ctx.f, ctx.tab, ctx.n_steps, x0, t0, t1,
                                 params, ctx.backend)


@register_gradient
@dataclasses.dataclass(frozen=True)
class RematSolve(GradientStrategy):
    """Whole-solve rematerialization, the paper's baseline scheme (exact;
    O(M) forward, O(N s L) inside the backward)."""
    name: ClassVar[str] = "remat_solve"
    capabilities: ClassVar[FrozenSet] = frozenset({_FIXED_T1, _FIXED_TS})

    def fixed(self, ctx, x0, t0, t1, params):
        return odeint_remat_solve(ctx.f, ctx.tab, ctx.n_steps, x0, t0, t1,
                                  params, ctx.backend)


@register_gradient
@dataclasses.dataclass(frozen=True)
class ContinuousAdjoint(GradientStrategy):
    """Chen et al. 2018 continuous adjoint: O(L) memory, approximate
    gradient (O(h^p) backward-integration error).

    steps_multiplier — fixed-grid backward solves take
                       ``n_steps * steps_multiplier`` steps (must be >= 1:
                       a zero-step backward solve silently returns garbage
                       gradients).
    bwd_adaptive     — controller for the adaptive backward solve of the
                       augmented system (defaults to the forward config).
    """
    name: ClassVar[str] = "adjoint"
    capabilities: ClassVar[FrozenSet] = frozenset(
        {_FIXED_T1, _FIXED_TS, _ADAPT_T1, _ADAPT_TS})
    batched_capabilities: ClassVar[FrozenSet] = frozenset(
        {_ADAPT_T1, _ADAPT_TS})

    steps_multiplier: int = 1
    bwd_adaptive: Optional[AdaptiveConfig] = None

    def __post_init__(self):
        if not isinstance(self.steps_multiplier, (int, np.integer)) \
                or isinstance(self.steps_multiplier, bool) \
                or self.steps_multiplier < 1:
            raise ValueError(
                "ContinuousAdjoint.steps_multiplier must be an int >= 1 "
                "(a zero-step backward solve returns garbage gradients); "
                f"got {self.steps_multiplier!r}")
        # normalize so the custom_vjp nondiff-arg hashing sees a plain int
        object.__setattr__(self, "steps_multiplier",
                           int(self.steps_multiplier))

    def fixed(self, ctx, x0, t0, t1, params):
        return odeint_adjoint(ctx.f, ctx.tab, ctx.n_steps,
                              self.steps_multiplier, ctx.backend,
                              x0, t0, t1, params)

    def adaptive(self, ctx, x0, t0, t1, params):
        return odeint_adjoint_adaptive(
            ctx.f, ctx.tab, ctx.adaptive,
            self.bwd_adaptive or ctx.adaptive, ctx.backend,
            x0, t0, t1, params)

    def adaptive_batched(self, ctx, x0, t0, t1, params):
        # per-lane forward AND backward grids; the backward augmented state
        # carries a per-lane grad-theta accumulator — O(B L) memory
        # (core/adjoint.py, docs/batching.md).
        return odeint_adjoint_adaptive_batched(
            ctx.f, ctx.tab, ctx.adaptive,
            self.bwd_adaptive or ctx.adaptive, ctx.backend,
            x0, t0, t1, params)
    # SaveAt value AND stats both come from the base class (batched and
    # not): generic restart-per-segment segmentation + the matching
    # restart replay.


# ---------------------------------------------------------------------------
# Capability matrix
# ---------------------------------------------------------------------------

def capability_matrix() -> Dict[str, Dict[Tuple[str, str], bool]]:
    """The full declarative (gradient x stepping x saveat) legality table,
    assembled from the registered strategies (docs/api.md renders it via
    tools/gen_capability_table.py)."""
    return {name: {(sk, vk): (sk, vk) in cls.capabilities
                   for sk in STEPPING_KINDS for vk in SAVEAT_KINDS}
            for name, cls in sorted(GRADIENT_REGISTRY.items())}


def batched_capability_matrix() -> Dict[str, Dict[Tuple[str, str], bool]]:
    """Same table for ``solve(..., batch_axis=0)``: which cells each
    strategy supports with masked per-lane step control (every fixed cell a
    strategy claims, plus its declared batched adaptive cells)."""
    return {name: {(sk, vk): (sk, vk) in cls.batched_cells()
                   for sk in STEPPING_KINDS for vk in SAVEAT_KINDS}
            for name, cls in sorted(GRADIENT_REGISTRY.items())}


def mesh_capability_matrix() -> Dict[str, Dict[Tuple[str, str], bool]]:
    """Same table for ``solve(..., batch_axis=0, mesh=...)``: the batched
    cells restricted to t1|ts saveat.  The mesh path shard_maps the SAME
    batched hooks (``fixed``/``fixed_saveat``/``adaptive_*_with_stats``),
    so every batched t1/ts cell is mesh-legal; dense output is not wired
    through shard_map."""
    return {name: {cell: ok and cell[1] in ("t1", "ts")
                   for cell, ok in cells.items()}
            for name, cells in batched_capability_matrix().items()}


def _check_capability(gradient: GradientStrategy, stepping_kind: str,
                      saveat_kind: str, batched: bool = False) -> None:
    cells = (type(gradient).batched_cells() if batched
             else type(gradient).capabilities)
    if (stepping_kind, saveat_kind) in cells:
        return
    name = type(gradient).name
    legal = ", ".join(f"{sk}+{vk}" for sk, vk in sorted(cells))
    ctx = " with batch_axis=0" if batched else ""
    raise ValueError(
        f"gradient {name!r} does not support stepping={stepping_kind!r} "
        f"with saveat={saveat_kind!r}{ctx}; legal (stepping+saveat) "
        f"combinations for {name!r}{ctx}: {legal}.  See the capability "
        "matrix in docs/api.md")


# ---------------------------------------------------------------------------
# solve
# ---------------------------------------------------------------------------

def _fixed_stats(tab: ButcherTableau, n_steps: int, n_segments: int,
                 lanes: Optional[int] = None):
    """Fixed-grid stats are exact static counts: the drivers skip the
    embedded error estimate, so the cost is exactly s f-evals per step.
    With ``lanes`` (batch_axis=0) the counts broadcast per lane — every
    lane takes the same deterministic grid."""
    total = n_segments * n_steps
    fevals = total * tab.s
    if lanes is None:
        return ({"n_steps": jnp.int32(total),
                 "n_fevals": jnp.int32(fevals),
                 "n_attempts": jnp.int32(total)}, jnp.asarray(True))
    return ({"n_steps": jnp.full((lanes,), total, jnp.int32),
             "n_fevals": jnp.full((lanes,), fevals, jnp.int32),
             "n_attempts": jnp.full((lanes,), total, jnp.int32)},
            jnp.ones((lanes,), bool))


def solve(f: VectorField, x0, params, *,
          saveat: Optional[SaveAt] = None,
          method: Union[str, ButcherTableau] = "dopri5",
          gradient: Union[str, GradientStrategy, None] = None,
          stepping: Union[int, AdaptiveConfig] = 16,
          backend: str = "auto",
          t0=0.0,
          batch_axis: Optional[int] = None,
          mesh=None,
          sharding=None) -> Solution:
    """Integrate ``dx/dt = f(x, t, params)`` and return a ``Solution``.

    f          — vector field over arbitrary pytrees; times are not
                 differentiated (zero cotangents), matching the paper's
                 fixed-T setting.
    saveat     — observation scheme (default ``SaveAt(t1=1.0)``).
    method     — tableau name or a ``ButcherTableau``.
    gradient   — a ``GradientStrategy`` (or registered name; default
                 ``SymplecticAdjoint()``).
    stepping   — int N (fixed grid; N steps per observation segment) or an
                 ``AdaptiveConfig`` (``max_steps`` per segment).
    backend    — stage-combine dispatch: auto | jnp | pallas
                 (core/combine.py).
    t0         — start time (keyword; default 0).
    batch_axis — None (default): ONE trajectory; a leading batch axis in
                 the state is part of that single trajectory's state, so
                 an adaptive controller pools its error norm over the
                 whole batch (lockstep).  0: the leading axis of every
                 state leaf indexes B INDEPENDENT trajectories — adaptive
                 solves run masked per-lane step control (each lane its
                 own accepted grid, error norm, and accept/reject; exact
                 per-lane gradients under the symplectic adjoint), and
                 ``stats``/``success`` become per-lane (B,) arrays.  Times
                 (``t0``, ``saveat``) stay shared.  Only axis 0 is
                 supported.  See docs/batching.md.
    mesh       — a ``jax.sharding.Mesh``: shard the lane axis over the
                 mesh's data axes (the longest divisible prefix of
                 ``("pod", "data")``) with ``shard_map``.  Requires
                 ``batch_axis=0`` and saveat t1|ts.  Per-lane controller
                 state stays shard-local; both exact backward passes
                 replay shard-locally with the param-cotangent psum as
                 the only real collective, and ``stats`` gains
                 ``shard_steps`` / ``load_imbalance``.  See
                 docs/parallel.md.
    sharding   — params placement under ``mesh``: None (replicated,
                 default), ``"auto"`` (``repro.parallel`` path rules), or
                 an explicit ``PartitionSpec`` pytree/prefix.
    """
    with jax.named_scope(scopes.ODE_SOLVE):
        tab = get_tableau(method) if isinstance(method, str) else method
        resolve_backend(backend)  # eager validation, single source
        gradient = as_gradient("symplectic" if gradient is None else gradient)
        saveat = SaveAt(t1=1.0) if saveat is None else saveat
        if batch_axis is not None and batch_axis != 0:
            raise ValueError(
                f"batch_axis={batch_axis!r}: only the leading axis "
                "(batch_axis=0) is supported — move the trajectory axis of "
                "every state leaf to axis 0")
        batched = batch_axis is not None
        lanes = lane_count(x0) if batched else None

        if isinstance(stepping, AdaptiveConfig):
            stepping_kind, n_steps, adaptive = "adaptive", None, stepping
        elif isinstance(stepping, (int, np.integer)) \
                and not isinstance(stepping, bool):
            if stepping < 1:
                raise ValueError(f"stepping={stepping}: a fixed-grid solve "
                                 "needs >= 1 steps")
            stepping_kind, n_steps, adaptive = "fixed", int(stepping), None
        else:
            raise TypeError(
                "stepping must be an int (fixed-grid step count) or an "
                f"AdaptiveConfig; got {type(stepping).__name__}")

        _check_capability(gradient, stepping_kind, saveat.kind, batched)
        t0 = jnp.asarray(t0, dtype=jnp.result_type(float))
        ctx = _Ctx(f, tab, n_steps, adaptive, backend)

        if mesh is None and sharding is not None:
            raise ValueError("solve(sharding=...) requires mesh=: the params "
                             "placement only means something on a mesh")
        if mesh is not None:
            if not batched:
                raise ValueError(
                    "solve(mesh=...) shards the lane axis over the mesh's "
                    "data axes: pass batch_axis=0 (a single trajectory has "
                    "no lane axis to shard — see docs/parallel.md)")
            return _solve_sharded(gradient, ctx, tab, n_steps,
                                  stepping_kind, saveat, x0, t0, params,
                                  lanes, mesh, sharding)

        if saveat.kind == "t1":
            t1 = jnp.asarray(saveat.t1, dtype=t0.dtype)
            if stepping_kind == "fixed":
                # the fixed grid is state-independent: the plain driver IS
                # the per-lane solve, only the stats shapes change.
                ys = gradient.fixed(ctx, x0, t0, t1, params)
                stats, success = _fixed_stats(tab, n_steps, 1, lanes)
            elif batched:
                ys, stats, success = gradient.adaptive_batched_with_stats(
                    ctx, x0, t0, t1, params)
            else:
                ys, stats, success = gradient.adaptive_with_stats(
                    ctx, x0, t0, t1, params)
            return Solution(ys=ys, final_state=ys, stats=stats,
                            success=success)

        ts = _as_ts(saveat.ts, t0.dtype, t0)
        if saveat.kind == "ts":
            if stepping_kind == "fixed":
                ys = gradient.fixed_saveat(ctx, x0, t0, ts, params)
                stats, success = _fixed_stats(tab, n_steps, ts.shape[0],
                                              lanes)
            elif batched:
                ys, stats, success = \
                    gradient.adaptive_saveat_batched_with_stats(
                        ctx, x0, t0, ts, params)
            else:
                ys, stats, success = gradient.adaptive_saveat_with_stats(
                    ctx, x0, t0, ts, params)
        else:  # dense
            ys, stats, success = gradient.dense_saveat_with_stats(
                ctx, x0, t0, ts, params)

        final = jax.tree_util.tree_map(lambda l: l[-1], ys)
        return Solution(ys=ys, final_state=final, stats=stats, success=success)


def _solve_sharded(gradient: GradientStrategy, ctx: _Ctx,
                   tab: ButcherTableau, n_steps: Optional[int],
                   stepping_kind: str, saveat: SaveAt, x0, t0, params,
                   lanes: int, mesh, sharding) -> Solution:
    """The mesh path of ``solve``: run the SAME dispatch as the unsharded
    batched solve, but as a shard-local body under ``shard_map`` — each
    shard solves its contiguous lane block exactly as a single-device call
    would (bitwise: values, per-lane stats, grids, h carries).  Lives here
    rather than in ``repro.parallel`` so the dispatch stays next to the
    unsharded branch it must mirror; the mesh mechanics (lane-axis
    selection, specs, load stats) come from ``repro.parallel.solve``.
    """
    from ..parallel import solve as _pps  # parallel imports core: lazy
    axes = _pps.lane_axes(mesh, lanes, require=True)
    n_shards = _pps.shard_count(mesh, axes)
    lanes_local = lanes // n_shards
    # rank-0 param leaves stay lifted to (1,) through the whole shard-local
    # driver (they are saved as custom_vjp residuals, and jax 0.4.37's
    # shard_map transpose cannot handle rank-0 residuals/inputs); only the
    # user field sees the original scalars.
    params, _restore, _lifted = _pps.lift_scalar_params(params)
    if _lifted:
        _f = ctx.f
        ctx = _Ctx(lambda x, t, p: _f(x, t, _restore(p)), ctx.tab,
                   ctx.n_steps, ctx.adaptive, ctx.backend)
    pspec = _pps.resolve_param_specs(params, mesh, sharding)

    if saveat.kind == "t1":
        t1 = jnp.asarray(saveat.t1, dtype=t0.dtype)
        if stepping_kind == "fixed":
            def body(x0_, params_):
                ys = gradient.fixed(ctx, x0_, t0, t1, params_)
                stats, success = _fixed_stats(tab, n_steps, 1, lanes_local)
                return ys, stats, success
        else:
            def body(x0_, params_):
                return gradient.adaptive_batched_with_stats(
                    ctx, x0_, t0, t1, params_)
        ys, stats, success = _pps.sharded_solve_triple(
            body, mesh, axes, x0, params, params_spec=pspec, ys_lane_axis=0)
        stats = _pps.with_shard_load_stats(stats, n_shards)
        return Solution(ys=ys, final_state=ys, stats=stats, success=success)

    if saveat.kind != "ts":
        # unreachable today (_check_capability rejects batched dense), but
        # the mesh path must never silently fall through to a new kind.
        raise ValueError(
            f"solve(mesh=...) supports saveat t1|ts; got {saveat.kind!r}")
    ts = _as_ts(saveat.ts, t0.dtype, t0)
    if stepping_kind == "fixed":
        def body(x0_, params_):
            ys = gradient.fixed_saveat(ctx, x0_, t0, ts, params_)
            stats, success = _fixed_stats(tab, n_steps, ts.shape[0],
                                          lanes_local)
            return ys, stats, success
    else:
        def body(x0_, params_):
            return gradient.adaptive_saveat_batched_with_stats(
                ctx, x0_, t0, ts, params_)
    # SaveAt stacks are time-major: lanes live on axis 1 of the ys leaves.
    ys, stats, success = _pps.sharded_solve_triple(
        body, mesh, axes, x0, params, params_spec=pspec, ys_lane_axis=1)
    stats = _pps.with_shard_load_stats(stats, n_shards)
    final = jax.tree_util.tree_map(lambda l: l[-1], ys)
    return Solution(ys=ys, final_state=final, stats=stats, success=success)
