"""Continuous-batching solve engine over a masked ``SolverState``.

The slot model (after JetStream's decode slots): the engine owns ONE
lane-batched ``SolverState`` of B slots and repeatedly applies the SAME
``AdaptiveStepper.advance`` the offline drivers run — AOT-compiled once per
lane bucket, with the state donated on every call so the slot buffers are
updated in place rather than reallocated.  A slot is either OCCUPIED (a
request mid-solve; its lane of the state is live controller state) or FREE
(an inactive lane — ``t0 == t1`` makes ``lanes_active`` False, so
``advance`` passes it through untouched at the cost of one wasted lane of
each fused f evaluation).

Requests are heterogeneous: each carries its own x0, [t0, t1] horizon, and
rtol/atol.  Tolerances ride the state as per-lane ARRAYS
(``SolverState.rtol``/``atol`` — tolerances as data), so one compiled
``advance`` serves every tolerance mix without recompilation, and the
per-leaf cast in ``_error_norm`` keeps each lane's accept/reject decisions
bit-identical to a single-trajectory solve at the same tolerances.

Insertion and eviction happen at step boundaries, against the RUNNING
state: ``_insert`` (jitted, donated) rewrites one lane — clock, state,
fresh h carry, zeroed counters and checkpoint columns — while every other
lane's mid-flight controller state is untouched.  Eviction reads a finished
lane's result off the state and marks the slot free host-side; the lane
itself is already self-masking (done lanes fail ``lanes_active``).

Bucketing: the engine starts at the smallest configured bucket and GROWS
through ``EngineConfig.buckets`` as concurrent demand (occupied + queued)
rises — each bucket's ``advance`` is AOT-compiled at init, so growth at a
step boundary is a pad, not a compile stall.  The engine never shrinks:
compaction would have to move live lanes between slots (and re-land their
checkpoint columns), and a mostly-free large state costs only wasted lane
slots per step, the same masked-lane price the offline batched driver
already pays (docs/batching.md).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.stepper import AdaptiveConfig, AdaptiveStepper, SolverState
from ..core.rk import rk_solve_adaptive
from ..core.tableau import ButcherTableau

Pytree = Any


class Request(NamedTuple):
    """One trajectory to solve: its own state, horizon, and tolerances."""
    x0: Pytree
    t0: float
    t1: float
    rtol: float
    atol: float


class Result(NamedTuple):
    """Harvested per-request outcome (host-side scalars + the final state)."""
    x_final: Pytree
    succeeded: bool
    n_accepted: int
    n_fevals: int
    n_attempts: int
    submitted_at: float      # perf_counter stamps; latency = completed -
    completed_at: float      # submitted (includes queue wait — serving time)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    buckets: tuple = (4, 8, 16)   # lane counts advance is AOT-compiled for
    check_every: int = 1          # advance calls between eviction sweeps
    mesh: Any = None              # jax.sharding.Mesh: slot state lives
    #                               lane-sharded over the mesh's data axes
    #                               (repro.parallel.solver_state_specs),
    #                               params replicated; every bucket must
    #                               fill whole lane shards.

    def __post_init__(self):
        if not self.buckets or list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError(f"buckets must be strictly increasing, got "
                             f"{self.buckets}")
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")
        if self.mesh is not None:
            from ..parallel.solve import lane_axes, shard_count
            axes = lane_axes(self.mesh, self.buckets[0], require=True)
            n = shard_count(self.mesh, axes)
            bad = [B for B in self.buckets if B % n]
            if bad:
                raise ValueError(
                    f"EngineConfig.mesh shards lanes {n}-way over axes "
                    f"{axes}, but bucket(s) {bad} are not divisible by {n}:"
                    " every AOT bucket's slot state must fill whole lane "
                    "shards")


def _map_lanes(state: SolverState, f_lane, f_buf) -> SolverState:
    """Apply ``f_lane`` to every lane-axis-0 field and ``f_buf`` to every
    step-major checkpoint buffer (lane axis 1) of a batched state."""
    return SolverState(
        t0=f_lane(state.t0), t1=f_lane(state.t1), t=f_lane(state.t),
        x=jax.tree_util.tree_map(f_lane, state.x), h=f_lane(state.h),
        n_accepted=f_lane(state.n_accepted),
        n_attempts=f_lane(state.n_attempts),
        n_fevals=f_lane(state.n_fevals),
        xs=jax.tree_util.tree_map(f_buf, state.xs),
        ts=f_buf(state.ts), hs=f_buf(state.hs),
        rtol=None if state.rtol is None else f_lane(state.rtol),
        atol=None if state.atol is None else f_lane(state.atol))


def params_from_checkpoint(directory: str, like: Pytree,
                           step: Optional[int] = None, shardings=None):
    """Load the params leaf out of a TRAINING checkpoint (the full
    ``train.TrainState`` contract saved by ``runtime.Checkpointer``).

    ``like`` must be a state with the same pytree structure as what
    training saved — e.g. ``train.init_train_state`` with the training
    arch/config (parameters are overwritten, so the init values don't
    matter).  Returns ``(params, step)``.  This is the train -> serve
    handoff: tests/test_failures.py proves a checkpoint written by
    ``launch.train`` boots serving with the trained weights.
    """
    from ..runtime import Checkpointer
    state, step = Checkpointer(directory).restore(like, step=step,
                                                  shardings=shardings)
    return state["params"], step


class SolveEngine:
    """Continuous-batching adaptive-solve server.

    ``submit`` enqueues requests; ``run`` drives the slot state until the
    queue and every occupied lane drain, returning {request_id: Result}.
    ``step`` exposes one fill -> advance -> evict boundary for tests and
    incremental driving.  All requests must share the template's state
    pytree structure/shapes (one compiled advance per bucket); values,
    horizons, and tolerances are free per request.
    """

    def __init__(self, f, tab: ButcherTableau, cfg: AdaptiveConfig, params,
                 x0_template: Pytree, engine_cfg: EngineConfig = None,
                 combine_backend: str = "auto"):
        self.stepper = AdaptiveStepper(f, tab, cfg, combine_backend)
        self.cfg = cfg
        self.engine_cfg = engine_cfg or EngineConfig()
        mesh = self.engine_cfg.mesh
        self._mesh = mesh
        if mesh is not None:
            from ..parallel.solve import lane_axes
            self._lane_shard_axes = lane_axes(
                mesh, self.engine_cfg.buckets[0], require=True)
            from jax.sharding import NamedSharding, PartitionSpec
            params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
        self.params = params
        self._template = jax.tree_util.tree_map(
            lambda l: jnp.zeros(jnp.shape(l), jnp.asarray(l).dtype),
            x0_template)
        self._treedef = jax.tree_util.tree_structure(self._template)
        self._queue: deque = deque()
        self._pending_meta: Dict[int, float] = {}
        self._next_rid = 0
        self._steps_total = 0
        self._inserted_while_running = 0
        buckets = tuple(self.engine_cfg.buckets)
        self._buckets = buckets
        self._advance: Dict[int, Any] = {}
        for B in buckets:
            proto = self._blank_state(B)
            self._advance[B] = (
                jax.jit(self.stepper.advance, donate_argnums=0)
                .lower(proto, params).compile())
        self._active_fn = jax.jit(self.stepper.lanes_active)
        self._insert_fn = jax.jit(self._insert, donate_argnums=0)
        self._harvest_fn = jax.jit(self._harvest)
        self._state = self._blank_state(buckets[0])
        self._lane_rid: List[Optional[int]] = [None] * buckets[0]
        self.restored_step: Optional[int] = None

    @classmethod
    def from_checkpoint(cls, f, tab: ButcherTableau, cfg: AdaptiveConfig,
                        directory: str, like: Pytree, x0_template: Pytree,
                        engine_cfg: EngineConfig = None,
                        combine_backend: str = "auto",
                        step: Optional[int] = None) -> "SolveEngine":
        """Boot an engine from a TRAINING checkpoint: the params leaf of
        the ``train.TrainState`` saved by ``launch.train`` becomes the
        field parameters (``like`` supplies the saved pytree structure,
        see ``params_from_checkpoint``)."""
        params, step = params_from_checkpoint(directory, like, step)
        engine = cls(f, tab, cfg, params, x0_template, engine_cfg,
                     combine_backend)
        engine.restored_step = step
        return engine

    # -- slot-state construction / resizing ---------------------------------
    def _blank_state(self, B: int) -> SolverState:
        """All-free state: t0 == t1 == 0 makes every lane inactive, so
        ``advance`` is the identity until something is inserted."""
        x0 = jax.tree_util.tree_map(
            lambda l: jnp.zeros((B,) + jnp.shape(l), l.dtype),
            self._template)
        state = self.stepper.init_state(
            x0, 0.0, 0.0, lanes=B, rtol=self.cfg.rtol, atol=self.cfg.atol)
        # Donation requires every leaf to own a DISTINCT buffer: eagerly
        # constructed equal constants (t0/t, the zeroed counters) can come
        # back aliased out of jax's constant handling, and donating the
        # same buffer twice is an Execute()-time error.  One explicit copy
        # per leaf at construction breaks the aliases; the advance/insert
        # executables keep them distinct from then on (donated pass-through
        # outputs alias their own distinct inputs).
        return self._commit(
            jax.tree_util.tree_map(lambda l: l.copy(), state))

    def _commit(self, state: SolverState) -> SolverState:
        """Land a slot state on its home layout: lane-sharded over the
        config mesh's data axes when one is set (docs/parallel.md), the
        identity otherwise.  Called wherever a state is (re)built outside
        the compiled path — construction, growth, post-insert — so the
        AOT-compiled ``advance`` always sees the shardings it was lowered
        for."""
        if self._mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.solve import solver_state_specs
        specs = solver_state_specs(state, self._lane_shard_axes)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s), specs,
            is_leaf=lambda s: isinstance(s, PartitionSpec))
        return jax.device_put(state, shardings)

    def _grow(self, new_B: int) -> None:
        B = self._lanes
        blank = self._blank_state(new_B - B)

        def pad0(l, b):
            return jnp.concatenate([l, b], axis=0)

        def pad1(l, b):
            return jnp.concatenate([l, b], axis=1)

        s, b = self._state, blank
        self._state = self._commit(SolverState(
            t0=pad0(s.t0, b.t0), t1=pad0(s.t1, b.t1), t=pad0(s.t, b.t),
            x=jax.tree_util.tree_map(pad0, s.x, b.x), h=pad0(s.h, b.h),
            n_accepted=pad0(s.n_accepted, b.n_accepted),
            n_attempts=pad0(s.n_attempts, b.n_attempts),
            n_fevals=pad0(s.n_fevals, b.n_fevals),
            xs=jax.tree_util.tree_map(pad1, s.xs, b.xs),
            ts=pad1(s.ts, b.ts), hs=pad1(s.hs, b.hs),
            rtol=pad0(s.rtol, b.rtol), atol=pad0(s.atol, b.atol)))
        self._lane_rid.extend([None] * (new_B - B))

    @property
    def _lanes(self) -> int:
        return len(self._lane_rid)

    # -- lane insert / harvest (jitted; lane index is traced data) ----------
    def _insert(self, state: SolverState, lane, x0, t0, t1, rtol, atol):
        """Rewrite ONE lane of a running state for a fresh request: clock at
        t0, fresh h carry (sign(t1-t0) * initial_step, the same seed a
        single solve with h0=None uses), zeroed counters and checkpoint
        columns.  Every other lane is untouched."""
        dtype = state.t.dtype
        t0 = jnp.asarray(t0, dtype)
        t1 = jnp.asarray(t1, dtype)
        h = jnp.sign(t1 - t0) * jnp.asarray(self.cfg.initial_step, dtype)
        zero = jnp.int32(0)
        return state._replace(
            t0=state.t0.at[lane].set(t0),
            t1=state.t1.at[lane].set(t1),
            t=state.t.at[lane].set(t0),
            x=jax.tree_util.tree_map(
                lambda buf, v: buf.at[lane].set(v.astype(buf.dtype)),
                state.x, x0),
            h=state.h.at[lane].set(h),
            n_accepted=state.n_accepted.at[lane].set(zero),
            n_attempts=state.n_attempts.at[lane].set(zero),
            n_fevals=state.n_fevals.at[lane].set(zero),
            xs=jax.tree_util.tree_map(
                lambda buf: buf.at[:, lane].set(jnp.zeros((), buf.dtype)),
                state.xs),
            ts=state.ts.at[:, lane].set(0.0),
            hs=state.hs.at[:, lane].set(0.0),
            rtol=state.rtol.at[lane].set(jnp.asarray(rtol, dtype)),
            atol=state.atol.at[lane].set(jnp.asarray(atol, dtype)))

    def _harvest(self, state: SolverState, lane):
        return (jax.tree_util.tree_map(lambda l: l[lane], state.x),
                self.stepper.succeeded(state)[lane],
                state.n_accepted[lane], state.n_fevals[lane],
                state.n_attempts[lane])

    # -- public API ---------------------------------------------------------
    def submit(self, request: Request) -> int:
        if jax.tree_util.tree_structure(request.x0) != self._treedef:
            raise ValueError("request x0 pytree structure does not match "
                             "the engine's template")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, request, time.perf_counter()))
        return rid

    @property
    def occupancy(self) -> int:
        return sum(rid is not None for rid in self._lane_rid)

    @property
    def pending(self) -> int:
        return len(self._queue)

    def _fill(self) -> None:
        demand = self.occupancy + len(self._queue)
        target = self._lanes
        for B in self._buckets:
            if B >= min(demand, self._buckets[-1]):
                target = max(self._lanes, B)
                break
        else:
            target = self._buckets[-1]
        if target > self._lanes:
            self._grow(target)
        running = self.occupancy > 0
        for lane in range(self._lanes):
            if not self._queue:
                break
            if self._lane_rid[lane] is not None:
                continue
            rid, req, t_sub = self._queue.popleft()
            self._state = self._commit(self._insert_fn(
                self._state, lane, req.x0, req.t0, req.t1, req.rtol,
                req.atol))
            self._lane_rid[lane] = rid
            self._pending_meta[rid] = t_sub
            if running:
                self._inserted_while_running += 1
            running = True

    def _evict(self, results: Dict[int, Result]) -> None:
        active = jax.device_get(self._active_fn(self._state))
        now = time.perf_counter()
        for lane, rid in enumerate(self._lane_rid):
            if rid is None or active[lane]:
                continue
            x, ok, n_acc, fe, n_try = jax.device_get(
                self._harvest_fn(self._state, lane))
            results[rid] = Result(x, bool(ok), int(n_acc), int(fe),
                                  int(n_try), self._pending_meta.pop(rid),
                                  now)
            self._lane_rid[lane] = None

    def step(self, results: Dict[int, Result]) -> None:
        """One step boundary: fill free lanes, one donated AOT advance over
        the whole slot state, evict finished lanes (every ``check_every``
        boundaries)."""
        self._fill()
        self._state = self._advance[self._lanes](self._state, self.params)
        self._steps_total += 1
        if self._steps_total % self.engine_cfg.check_every == 0:
            self._evict(results)

    def run(self, requests=None) -> Dict[int, Result]:
        """Drain the queue (plus ``requests``, submitted first): returns
        {request_id: Result} once every lane is free again."""
        for r in requests or []:
            self.submit(r)
        results: Dict[int, Result] = {}
        while self._queue or self.occupancy:
            self.step(results)
        self._evict(results)   # catch lanes finished between sweeps
        return results

    @property
    def compiled_advance(self) -> Dict[int, Any]:
        """{bucket: the AOT-compiled ``advance`` executable} (read-only)."""
        return dict(self._advance)

    @property
    def stats(self) -> Dict[str, int]:
        return {"steps_total": self._steps_total,
                "lanes": self._lanes,
                "inserted_while_running": self._inserted_while_running}


def serve_timed(engine: SolveEngine, requests,
                arrivals=None) -> Dict[int, Result]:
    """Drive ``engine`` over ``requests`` with optional arrival pacing.

    ``arrivals`` is a monotone array of offsets in seconds from the start
    (``poisson_arrivals``): each request is submitted once its arrival time
    has passed, so reported latencies include real queue wait under the
    offered load.  ``arrivals=None`` submits everything up front (drain
    mode — equivalent to ``engine.run(requests)``).
    """
    if arrivals is None:
        return engine.run(requests)
    if len(arrivals) != len(requests):
        raise ValueError("one arrival time per request required")
    results: Dict[int, Result] = {}
    start = time.perf_counter()
    i = 0
    while i < len(requests) or engine.pending or engine.occupancy:
        now = time.perf_counter() - start
        while i < len(requests) and arrivals[i] <= now:
            engine.submit(requests[i])
            i += 1
        if engine.pending or engine.occupancy:
            engine.step(results)
        else:                       # idle: nothing in flight, wait it out
            time.sleep(min(float(arrivals[i]) - now, 0.01))
    return results


def naive_sequential_solve(f, tab, cfg: AdaptiveConfig, params, requests,
                           combine_backend: str = "auto",
                           warmup: bool = True):
    """The no-batching baseline: one jitted single-trajectory solve per
    request, sequentially.  Tolerances are closed into the trace exactly as
    the offline drivers do, so each DISTINCT (rtol, atol) pair costs one
    compile; ``warmup`` (default) runs each solver once untimed first, so
    the reported numbers measure steady-state solving, not compilation.
    Returns (results, per-request wall seconds)."""
    cache: Dict[tuple, Any] = {}

    def solver_for(rtol, atol):
        key = (float(rtol), float(atol))
        if key not in cache:
            c = dataclasses.replace(cfg, rtol=key[0], atol=key[1])
            cache[key] = jax.jit(
                lambda x0, t0, t1, p: rk_solve_adaptive(
                    f, tab, x0, t0, t1, p, c, combine_backend))
        return cache[key]

    if warmup:
        for req in requests:
            sol = solver_for(req.rtol, req.atol)(req.x0, req.t0, req.t1,
                                                 params)
        jax.block_until_ready(sol.x_final)

    results, lat = [], []
    for req in requests:
        solver = solver_for(req.rtol, req.atol)
        t0 = time.perf_counter()
        sol = solver(req.x0, req.t0, req.t1, params)
        jax.block_until_ready(sol.x_final)
        lat.append(time.perf_counter() - t0)
        results.append(sol)
    return results, lat
