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
                 hermite_observe, lane_count, rk_solve_adaptive,
                 rk_solve_segment, rk_solve_segments, segment_starts)
from .symplectic import symplectic_solve
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
    """Static per-solve context handed to every strategy hook.

    ``n_steps`` (fixed grid) xor ``adaptive`` (an ``AdaptiveConfig``) is
    the stepping; ``saveat`` is the observation kind ("t1", "ts" or
    "dense"); ``lanes`` is the lane count under ``batch_axis=0`` (the
    shard-local count on a mesh) and None for one trajectory."""
    __slots__ = ("f", "tab", "n_steps", "adaptive", "backend", "saveat",
                 "lanes")

    def __init__(self, f: VectorField, tab: ButcherTableau,
                 n_steps: Optional[int], adaptive: Optional[AdaptiveConfig],
                 backend: str, saveat: str = "t1",
                 lanes: Optional[int] = None):
        self.f = f
        self.tab = tab
        self.n_steps = n_steps
        self.adaptive = adaptive
        self.backend = backend
        self.saveat = saveat
        self.lanes = lanes

    @property
    def stepping(self) -> Union[int, AdaptiveConfig]:
        return self.n_steps if self.adaptive is None else self.adaptive

    @property
    def dense(self) -> bool:
        return self.saveat == "dense"


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


def _counters(sol, segmented: bool):
    """``(stats, success)`` of a stepper solution: per segment summed when
    ``segmented`` (a leading segment axis), per lane when lane-batched."""
    stats = {"n_steps": sol.n_accepted, "n_fevals": sol.n_fevals,
             "n_attempts": sol.n_attempts}
    if not segmented:
        return stats, sol.succeeded
    return ({k: jnp.sum(v, axis=0) for k, v in stats.items()},
            jnp.all(sol.succeeded, axis=0))


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


def _solve_stats(ctx: _Ctx, x0, t0, tobs, params, threaded: bool):
    """``(stats, success)`` of a ``t1``/``ts`` solve whose value came from
    a driver that does not expose its counters.  A fixed grid's are static
    counts.  An adaptive solve's come from replaying the controller once
    under stop_gradient with the exact arguments of the value's forward
    pass — the counters match the value solve bit-for-bit, and under jit
    XLA CSE/DCE collapses the duplicate.  ``threaded`` says which ``ts``
    sequence the value realized: the controller step threading across the
    observation boundaries (``rk_solve_segments``), or restarting at each
    (generic segmentation over the ``adaptive`` hook)."""
    segmented = ctx.saveat == "ts"
    if ctx.adaptive is None:
        return _fixed_stats(ctx.tab, ctx.n_steps,
                            tobs.shape[0] if segmented else 1, ctx.lanes)
    x0 = jax.lax.stop_gradient(x0)
    params = jax.lax.stop_gradient(params)
    lanes = ctx.lanes is not None
    if segmented:
        _, sol = rk_solve_segments(ctx.f, ctx.tab, x0, t0, tobs, params,
                                   ctx.adaptive, ctx.backend, lanes=lanes,
                                   threaded=threaded)
    else:
        sol = rk_solve_segment(ctx.f, ctx.tab, x0, t0, tobs, params,
                               ctx.adaptive, ctx.backend, lanes=lanes)
    return _counters(sol, segmented)


_FIXED_T1 = ("fixed", "t1")
_FIXED_TS = ("fixed", "ts")
_ADAPT_T1 = ("adaptive", "t1")
_ADAPT_TS = ("adaptive", "ts")
_ADAPT_DENSE = ("adaptive", "dense")


class GradientStrategy:
    """Base class for gradient strategies.

    A strategy declares its legal (stepping, saveat) cells in
    ``capabilities`` and implements the hooks for the cells it supports.
    There are three:

    * ``fixed(ctx, x0, t0, t1, params)`` and ``adaptive(...)`` — the
      differentiable value of ONE segment [t0, t1] on a fixed grid or under
      ``ctx.adaptive`` (on lanes when ``ctx.lanes`` is set);
    * ``observe(ctx, x0, t0, tobs, params) -> (ys, stats, success)`` — the
      whole solve: ``tobs`` is ``t1`` or the observation times ``ts`` as
      ``ctx.saveat`` says.  The default chains the segment hooks by generic
      checkpointed segmentation (the adaptive controller restarts at each
      boundary) and reads the stats from ``_solve_stats``.  A strategy with
      a whole-solve driver overrides it.

    So a minimal new strategy is ``name`` + ``capabilities`` + ``fixed``
    (and ``adaptive`` if claimed).  Register it with ``@register_gradient``;
    ``solve`` needs no edits.
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

    def fixed(self, ctx: _Ctx, x0, t0, t1, params):
        raise NotImplementedError

    def adaptive(self, ctx: _Ctx, x0, t0, t1, params):
        raise NotImplementedError

    def observe(self, ctx: _Ctx, x0, t0, tobs, params):
        if ctx.dense:
            # dense output has no segment form: a strategy claiming
            # ('adaptive', 'dense') overrides observe
            raise NotImplementedError
        hook = self.fixed if ctx.adaptive is None else self.adaptive

        def one(x, a, b):
            return hook(ctx, x, a, b, params)

        if ctx.saveat == "t1":
            ys = one(x0, t0, tobs)
        else:
            ys = _segmented(one, x0, t0, tobs)
        return (ys,) + _solve_stats(ctx, x0, t0, tobs, params,
                                    threaded=False)


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

    def observe(self, ctx, x0, t0, tobs, params):
        ys = symplectic_solve(ctx.f, ctx.tab, ctx.stepping, ctx.backend,
                              x0, t0, tobs, params,
                              lanes=ctx.lanes is not None,
                              segmented=ctx.saveat == "ts")
        return (ys,) + _solve_stats(ctx, x0, t0, tobs, params,
                                    threaded=True)


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

    # adaptive: the value drivers ARE the controllers, so value and stats
    # come from ONE run — no replay (this is also what keeps the
    # odeint_with_stats shim at its historical single-solve cost).
    def observe(self, ctx, x0, t0, tobs, params):
        cfg = ctx.adaptive
        if cfg is None:
            return super().observe(ctx, x0, t0, tobs, params)
        if ctx.dense:
            # ONE unsegmented solve + Hermite interpolation: value and
            # stats come from the same controller run (2 extra f-evals per
            # observation for the endpoint slopes).
            sol = rk_solve_adaptive(ctx.f, ctx.tab, x0, t0, tobs[-1], params,
                                    cfg, ctx.backend)
            obs = hermite_observe(ctx.f, ctx.tab, sol, params, tobs,
                                  ctx.backend)
            ys = apply_on_failure(obs, sol.succeeded, cfg.on_failure)
            stats = {"n_steps": sol.n_accepted,
                     "n_fevals": sol.n_fevals + 2 * tobs.shape[0],
                     "n_attempts": sol.n_attempts}
            return ys, stats, sol.succeeded
        lanes = ctx.lanes is not None
        if ctx.saveat == "ts":
            ys, sol = rk_solve_segments(ctx.f, ctx.tab, x0, t0, tobs, params,
                                        cfg, ctx.backend, lanes=lanes)
            return (ys,) + _counters(sol, True)
        sol = rk_solve_segment(ctx.f, ctx.tab, x0, t0, tobs, params, cfg,
                               ctx.backend, lanes=lanes)
        ys = apply_on_failure(sol.x_final, sol.succeeded, cfg.on_failure)
        return (ys,) + _counters(sol, False)


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
        # on lanes: per-lane forward AND backward grids; the backward
        # augmented state carries a per-lane grad-theta accumulator —
        # O(B L) memory (core/adjoint.py, docs/batching.md).
        drive = (odeint_adjoint_adaptive if ctx.lanes is None
                 else odeint_adjoint_adaptive_batched)
        return drive(ctx.f, ctx.tab, ctx.adaptive,
                     self.bwd_adaptive or ctx.adaptive, ctx.backend,
                     x0, t0, t1, params)
    # SaveAt value AND stats both come from the base ``observe``: generic
    # restart-per-segment segmentation + the matching restart replay.


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
    solve body (the strategy's ``observe``) as the unsharded batched solve,
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
        if mesh is None and sharding is not None:
            raise ValueError("solve(sharding=...) requires mesh=: the params "
                             "placement only means something on a mesh")
        if mesh is not None and not batched:
            raise ValueError(
                "solve(mesh=...) shards the lane axis over the mesh's "
                "data axes: pass batch_axis=0 (a single trajectory has "
                "no lane axis to shard — see docs/parallel.md)")
        t0 = jnp.asarray(t0, dtype=jnp.result_type(float))
        if saveat.kind == "t1":
            tobs = jnp.asarray(saveat.t1, dtype=t0.dtype)
        else:
            tobs = _as_ts(saveat.ts, t0.dtype, t0)

        # The one solve body, run as it is or shard-locally on a mesh.
        def body(x0_, params_, field=f):
            ctx = _Ctx(field, tab, n_steps, adaptive, backend, saveat.kind,
                       lane_count(x0_) if batched else None)
            return gradient.observe(ctx, x0_, t0, tobs, params_)

        if mesh is None:
            ys, stats, success = body(x0, params)
        else:
            ys, stats, success = _solve_sharded(
                body, f, x0, params, lanes, mesh, sharding,
                ys_lane_axis=0 if saveat.kind == "t1" else 1)
        final = (ys if saveat.kind == "t1"
                 else jax.tree_util.tree_map(lambda l: l[-1], ys))
        return Solution(ys=ys, final_state=final, stats=stats,
                        success=success)


def _solve_sharded(body: Callable, f: VectorField, x0, params, lanes: int,
                   mesh, sharding, ys_lane_axis: int):
    """The mesh path of ``solve``: the SAME solve ``body`` as the
    unsharded batched solve, run shard-locally under ``shard_map`` — each
    shard solves its contiguous lane block exactly as a single-device call
    would (bitwise: values, per-lane stats, grids, h carries).  The mesh
    mechanics (lane-axis selection, specs, load stats) come from
    ``repro.parallel.solve``; ``ys_lane_axis`` is 1 for the time-major
    SaveAt stacks.
    """
    from ..parallel import solve as _pps  # parallel imports core: lazy
    axes = _pps.lane_axes(mesh, lanes, require=True)
    n_shards = _pps.shard_count(mesh, axes)
    # rank-0 param leaves stay lifted to (1,) through the whole shard-local
    # driver (they are saved as custom_vjp residuals, and jax 0.4.37's
    # shard_map transpose cannot handle rank-0 residuals/inputs); only the
    # user field sees the original scalars.
    params, _restore, _lifted = _pps.lift_scalar_params(params)
    if _lifted:
        def local(x0_, params_):
            return body(x0_, params_,
                        field=lambda x, t, p: f(x, t, _restore(p)))
    else:
        local = body
    pspec = _pps.resolve_param_specs(params, mesh, sharding)
    ys, stats, success = _pps.sharded_solve_triple(
        local, mesh, axes, x0, params, params_spec=pspec,
        ys_lane_axis=ys_lane_axis)
    return ys, _pps.with_shard_load_stats(stats, n_shards), success
