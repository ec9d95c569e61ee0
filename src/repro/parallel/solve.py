"""Mesh-sharded masked-batch solving: lanes over the data axes.

`solve(..., batch_axis=0, mesh=...)` shards the lane axis of the masked
per-lane adaptive driver (docs/batching.md) over the mesh's data-parallel
axes with ``shard_map``.  The shape of the subsystem:

* Lanes are split contiguously over the longest *divisible prefix* of
  ``("pod", "data")`` present in the mesh (`lane_axes`); each shard runs
  the SAME local program a single-device solve of its lane block would run,
  so per-lane values, stats, grids and h carries are bitwise identical to
  the unsharded solve of that block.
* All per-lane controller state (``SolverState.t/h/rtol/atol/n_*`` and the
  checkpoint buffers) lives shard-local inside the ``shard_map`` body —
  the forward pass contains NO cross-device communication.
* Both exact backward passes (the symplectic Algorithm-2 replay and the
  continuous adjoint) replay each lane's accepted grid shard-locally; the
  only cross-device collectives in the backward jaxpr are the ``psum``s
  that reduce the replicated-input cotangents (one per param leaf, plus
  the structurally-zero time cotangents) over the lane axes.  That
  contract is asserted jaxpr-level by ``repro.analysis``'s
  ``collective-count`` probe (docs/parallel.md).
* ``check_vma=False`` throughout: the adaptive driver is a
  ``lax.while_loop`` and shard_map has no replication rule for it.

``core/api.py::_solve_sharded`` wraps the SAME solve body the unsharded
batched solve runs (the strategy's ``observe``) with the helpers below.
The gradient path additionally requires the custom_vjp drivers (the one
symplectic driver and the continuous-adjoint drivers) to expose rank-1
time inputs — see ``repro.core.rk.time_lift``.
"""
from __future__ import annotations

import warnings
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.stepper import BatchedAdaptiveSolution, SolverState

#: Mesh axes a batch's lane dim may shard over, in precedence order.
DATA_AXES: Tuple[str, ...] = ("pod", "data")


def lane_axes(mesh, batch: int, axes: Sequence[str] = DATA_AXES, *,
              require: bool = False) -> Tuple[str, ...]:
    """Longest divisible prefix of the data axes for a ``batch``-sized dim.

    Returns the longest prefix of ``axes`` (restricted to axes present in
    ``mesh``) whose total size divides ``batch`` — so a batch that is not
    divisible by the FULL dp product still shards over the axes it can
    fill (e.g. B=6 on a (2, 2) ("pod", "data") mesh shards over "pod"
    alone), instead of silently replicating.  Warns whenever axes are
    dropped; with ``require=True`` an empty result (nothing divides)
    raises instead of degrading to a replicated no-op.
    """
    present = tuple(a for a in axes if a in mesh.shape)
    chosen = present
    while chosen and batch % int(
            np.prod([mesh.shape[a] for a in chosen])) != 0:
        chosen = chosen[:-1]
    if not chosen and require:
        detail = (f"no prefix of its data axes {present} divides the "
                  f"batch dim {batch}" if present
                  else f"mesh axes {tuple(mesh.shape)} contain none of the "
                       f"data axes {tuple(axes)}")
        raise ValueError(
            f"cannot shard the lane axis: {detail}.  Pad the batch or "
            "pick a mesh whose leading data axis divides it")
    if chosen != present:
        full = int(np.prod([mesh.shape[a] for a in present]))
        warnings.warn(
            f"batch dim {batch} is not divisible by the full "
            f"data-parallel product {full} of mesh axes {present}; "
            + (f"sharding over the divisible prefix {chosen} "
               f"(size {int(np.prod([mesh.shape[a] for a in chosen]))})"
               if chosen else "no prefix divides — lanes replicated"),
            stacklevel=2)
    return chosen


def shard_count(mesh, axes: Sequence[str]) -> int:
    """Number of lane shards a mesh realizes over ``axes``."""
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _bcast_spec(tree, spec: P):
    """Broadcast one spec over every leaf of a pytree."""
    return jax.tree_util.tree_map(lambda _: spec, tree)


def lane_spec(axes: Sequence[str], lane_axis: int = 0) -> P:
    """PartitionSpec placing the lane axes at position ``lane_axis``."""
    if not axes:
        return P()
    return P(*([None] * lane_axis), tuple(axes))


def batched_solution_specs(axes: Sequence[str]) -> BatchedAdaptiveSolution:
    """Specs for a ``BatchedAdaptiveSolution``: per-lane leaves on the lane
    axes, step-major checkpoint buffers (max_steps, B, ...) on axis 1."""
    lane = lane_spec(axes)
    step = lane_spec(axes, lane_axis=1)
    return BatchedAdaptiveSolution(
        x_final=lane, xs=step, ts=step, hs=step, n_accepted=lane,
        n_fevals=lane, succeeded=lane, h_final=lane, n_attempts=lane)


def solver_state_specs(state: SolverState, axes: Sequence[str]
                       ) -> SolverState:
    """Specs for a ``SolverState`` (the serve engine's resident state):
    per-lane controller fields on the lane axes, step-major checkpoint
    buffers on axis 1.  Shape-aware — a lane-batched state has (B,)
    horizons (per-lane t0/t1: the engine's heterogeneous requests) while a
    single state's scalar fields replicate."""
    lane = lane_spec(axes)
    step = lane_spec(axes, lane_axis=1)

    def per_lane(leaf):
        return P() if jnp.ndim(leaf) == 0 else lane

    def per_step(leaf):
        # (max_steps,) buffers of an unbatched state have no lane axis
        return step if jnp.ndim(leaf) >= 2 else P()

    return SolverState(
        t0=per_lane(state.t0), t1=per_lane(state.t1), t=per_lane(state.t),
        x=jax.tree_util.tree_map(per_lane, state.x), h=per_lane(state.h),
        n_accepted=per_lane(state.n_accepted),
        n_attempts=per_lane(state.n_attempts),
        n_fevals=per_lane(state.n_fevals),
        xs=jax.tree_util.tree_map(per_step, state.xs),
        ts=per_step(state.ts), hs=per_step(state.hs),
        rtol=None if state.rtol is None else per_lane(state.rtol),
        atol=None if state.atol is None else per_lane(state.atol))


def lift_scalar_params(params):
    """Reshape rank-0 param leaves to ``(1,)`` for the shard_map boundary.

    jax 0.4.37's shard_map transpose mishandles rank-0 differentiable
    inputs (the same ``_SpecError`` the rank-1 time refactor in
    ``repro.core.rk.time_lift`` works around), so a scalar param leaf —
    e.g. a global gain — would break ``grad`` of a sharded solve.  Returns
    ``(lifted, restore, has_scalar)``: the lifted tree crosses the
    shard_map boundary, ``restore`` undoes the lift inside the body, and
    ``has_scalar=False`` means both are identities (no jaxpr change for
    the common all-array case).  The cotangent psum for a lifted leaf has
    operand shape ``(1,)`` rather than ``()``.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    scalar = tuple(jnp.ndim(l) == 0 for l in leaves)
    if not any(scalar):
        return params, (lambda p: p), False
    lifted = treedef.unflatten(
        [jnp.reshape(l, (1,)) if s else l for l, s in zip(leaves, scalar)])

    def restore(params_):
        ls = treedef.flatten_up_to(params_)
        return treedef.unflatten(
            [jnp.reshape(l, ()) if s else l for l, s in zip(ls, scalar)])

    return lifted, restore, True


def resolve_param_specs(params, mesh, sharding):
    """The params in_spec for a sharded solve.

    ``None`` replicates (the default, and the only layout under which the
    shard-local replay is collective-free); ``"auto"`` applies the
    ``shardings.param_specs`` path rules (on a data-only mesh these resolve
    to replication — the wiring exists for meshes that add a model axis);
    anything else is taken as an explicit spec pytree (or prefix) matching
    ``params``.
    """
    if sharding is None:
        return P()
    if sharding == "auto":
        from .shardings import param_specs
        return param_specs(params, mesh)
    return sharding


def sharded_solve_triple(body, mesh, axes: Sequence[str], x0, params, *,
                         params_spec=None, ys_lane_axis: int = 0):
    """shard_map a local ``(ys, stats, success)`` solve body over lanes.

    ``body(x0_local, params)`` must be the LOCAL solve — exactly what a
    single-device call would run on one shard's lane block.  ``x0`` leaves
    shard on axis 0; ``ys`` leaves shard on ``ys_lane_axis`` (0 for t1
    output, 1 for time-major SaveAt stacks); stats and success are per-lane
    and shard on axis 0.
    """
    lane = lane_spec(axes)
    pspec = P() if params_spec is None else params_spec
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(lane, pspec),
        out_specs=(lane_spec(axes, ys_lane_axis), lane, lane),
        check_vma=False)(x0, params)


def with_shard_load_stats(stats: dict, n_shards: int) -> dict:
    """Attach the cross-shard load-imbalance metric to a solve's stats.

    ``shard_steps`` is each shard's total accepted-step count (lanes are
    contiguous blocks, so a reshape-sum over the gathered per-lane counts
    recovers the per-shard totals without any collective); the adaptive
    while-loop runs until the SLOWEST lane of each shard finishes, so
    ``load_imbalance`` = max/mean of ``shard_steps`` approximates the
    wall-clock cost of heterogeneous stiffness across shards (1.0 =
    perfectly balanced).
    """
    shard_steps = jnp.sum(
        jnp.reshape(stats["n_steps"], (n_shards, -1)), axis=1)
    ftype = jnp.result_type(float)
    mean = jnp.mean(shard_steps.astype(ftype))
    imbalance = jnp.where(mean > 0,
                          jnp.max(shard_steps).astype(ftype) / mean,
                          jnp.ones((), ftype))
    out = dict(stats)
    out["shard_steps"] = shard_steps
    out["load_imbalance"] = imbalance
    return out
