"""The symplectic adjoint method (the paper's contribution).

Forward (Algorithm 1): integrate with any explicit Runge-Kutta tableau,
retaining ONLY the step checkpoints {x_n, t_n, h_n} — these become the
custom_vjp residuals, so no stage computation graph survives the forward pass.

Backward (Algorithm 2 + Eq. (7)/(8)): for each step n = N-1..0,
  1. recompute the stage states X_{n,i} from the checkpoint x_n (lines 3-7),
  2. run the symplectic-partner stage recursion i = s..1 (lines 8-13):

        Lambda_{n,i} = lambda_{n+1} - h * sum_{j>i} btilde_j (a_{j,i}/b_i) l_j   (i not in I0)
        Lambda_{n,i} = - sum_{j>i} btilde_j a_{j,i} l_j                          (i in I0)
        l_{n,i}      = -(df/dx(X_{n,i}))^T Lambda_{n,i}
        btilde_i     = b_i  (i not in I0),   h_n  (i in I0 = {i: b_i = 0})

     each l_{n,i} is ONE jax.vjp of ONE network evaluation, and
  3. lambda_n = lambda_{n+1} - h * sum_i btilde_i l_{n,i};
     grad_theta += h * sum_i btilde_i (df/dtheta(X_{n,i}))^T Lambda_{n,i}.

Because the partitioned pair (forward RK, Eq. (7)) is symplectic, the bilinear
invariant lambda^T delta is conserved exactly in discrete time (Theorem 2), so
lambda_0 equals the EXACT gradient of the discrete forward map — verified
against jax.grad-through-the-solver to rounding error in tests.

The adjoint slopes l_{n,i} live in a stacked buffer (leading stage dim per
leaf), and both the Lambda recursion and the lambda_n update are row combines
through the StageCombiner (core/combine.py) — the same fused one-HBM-pass
primitive (jnp oracle or Pallas kernel) the forward solve uses, with the
h-dependent Eq. (7)/(8) coefficient rows precomputed per tableau.

Memory note (the paper's point, realized in XLA dataflow): the stage-i VJP's
residuals are forced to be live one-at-a-time by threading the previous
adjoint slope through ``lax.optimization_barrier`` into the stage state, so
neither CSE nor the scheduler can hoist all s recomputation graphs at once.
Live memory is O(N + s + L), not O(N * s * L).

Fields that read one slice of their parameters: a ``SlicedField``
(core/rk.py) declares the leading-axis slice ``index(t)`` it reads and the
field ``apply`` on that slice, as the NODE-mode LM's depth field does (one
layer of the stacked units per time).  ``symplectic_step_adjoint`` then
takes each stage's VJP with respect to that one slice and adds
``h btilde_i`` times its cotangent into the carried gradient at the stage's
own index, in place: the per-step parameter cotangent is one slice, not a
zero-filled stack with one slice set.  Every other field (the CNF, MLP
fields, a lambda wrapping a declared field) takes the dense path: the VJP
with respect to the whole ``params`` and a whole-tree sum per step.  The
lane-batched step (``symplectic_step_adjoint_lanes``, whose lanes may read
different slices) and the continuous adjoint (core/adjoint.py) always take
the dense path.

One driver serves every cell (``symplectic_solve``): its static arguments
are the field, the tableau, the stepping (an ``int`` fixed grid or an
``AdaptiveConfig``), the combine backend, whether the state is a batch of
lanes, and whether the solve is observed at the times ``ts`` (segmented)
or at ``t1`` alone.  Its forward pass is the stepper solve (core/rk.py:
``rk_solve_segment`` for ``t1``, ``rk_solve_segments`` for ``ts``) and
keeps Algorithm 1's checkpoints as the residuals; its backward pass is
``symplectic_replay``, a function of those checkpoints alone.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..runtime import scopes
from .combine import StageCombiner, alloc_stages, get_combiner, set_stage
from .rk import (AdaptiveConfig, SlicedField, VectorField, apply_on_failure,
                 lane_bcast, rk_solve_segment, rk_solve_segments, rk_stages,
                 take_slice, time_lift as _lift, time_unlift as _unlift,
                 time_zero_cotangent as _time_zero)
from .tableau import ButcherTableau

Pytree = Any


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _accumulate(gtheta, gstep):
    """The parameter gradient's running sum over the backward steps."""
    with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
        return _tree_add(gtheta, gstep)


def _tree_zeros(t):
    return jax.tree_util.tree_map(jnp.zeros_like, t)


def _barrier_with(x: Pytree, dep: Pytree) -> Pytree:
    """Return x, data-dependent on dep, opaque to CSE/scheduling."""
    leaves, treedef = jax.tree_util.tree_flatten((x, dep))
    leaves = jax.lax.optimization_barrier(leaves)
    x_out, _ = jax.tree_util.tree_unflatten(treedef, leaves)
    return x_out


def _scaled(c, tree):
    """``c * tree`` per leaf, ``c`` cast to each leaf's dtype."""
    return jax.tree_util.tree_map(
        lambda g: jnp.asarray(c, dtype=g.dtype) * g, tree)


def _add_into_slice(gtheta, k, contrib):
    """``gtheta[k] += contrib`` per leaf: a read, an add and a write of one
    leading-axis slice, which XLA updates in place in the carried buffer."""
    return jax.tree_util.tree_map(
        lambda G, g: jax.lax.dynamic_update_index_in_dim(
            G, jax.lax.dynamic_index_in_dim(G, k, 0, keepdims=False) + g,
            k, 0),
        gtheta, contrib)


def symplectic_step_adjoint(f: VectorField, tab: ButcherTableau,
                            x_n, t_n, h, params, lam_next, gtheta,
                            combiner: Optional[StageCombiner] = None):
    """One backward step of Algorithm 2.

    Returns ``(lambda_n, gtheta)``: the adjoint at the step's start, and the
    running parameter gradient ``gtheta`` with this step's
    ``h sum_i btilde_i (df/dtheta(X_{n,i}))^T Lambda_{n,i}`` added.

    A ``SlicedField`` (one leading-axis slice of ``params`` per time) takes
    each stage's VJP with respect to the slice ``f.index(t_{n,i})`` it
    reads and adds ``h btilde_i`` times that slice's cotangent into
    ``gtheta`` at the stage's own index (stages may read different
    slices).  Any other field takes the VJP with respect to the whole
    ``params`` and adds the step's dense sum.
    """
    combiner = combiner or get_combiner(tab)
    s = tab.s
    b, c = tab.b, tab.c
    sliced = isinstance(f, SlicedField)
    # --- Alg.2 lines 3-7: recompute stages from the checkpoint ----------
    Xs, _K = rk_stages(f, tab, x_n, t_n, h, params, combiner)

    def btilde(i):
        # Eq. (8): h_n replaces vanishing weights.
        return h if b[i] == 0.0 else b[i]

    L = alloc_stages(s, lam_next)   # stacked adjoint slopes l_{n,i}
    gstep = None
    dep = lam_next  # scheduling dependency chain (see module docstring)
    for i in reversed(range(s)):
        # --- Eq. (7): Lambda_{n,i} from the slope-buffer suffix L[i+1:] --
        Lam_i = combiner.lambda_stage(lam_next, L, h, i)
        # --- Alg.2 lines 10-12: one VJP of one network evaluation -------
        Xi = _barrier_with(Xs[i], dep)
        t_i = t_n + c[i] * h
        if sliced:
            k = f.index(t_i)
            _, vjp_fn = jax.vjp(lambda X, th: f.apply(X, t_i, th), Xi,
                                take_slice(params, k))
        else:
            _, vjp_fn = jax.vjp(lambda X, th: f(X, t_i, th), Xi, params)
        xbar, thbar = vjp_fn(Lam_i)
        l_i = jax.tree_util.tree_map(jnp.negative, xbar)
        L = set_stage(L, i, l_i)
        with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
            contrib = _scaled(btilde(i), thbar)
            if sliced:
                gtheta = _add_into_slice(gtheta, k, _scaled(h, contrib))
            else:
                gstep = contrib if gstep is None else _tree_add(gstep,
                                                                contrib)
        dep = l_i
    # --- lambda_n = lambda_{n+1} - h sum_i btilde_i l_{n,i} --------------
    lam_n = combiner.lambda_update(lam_next, L, h)
    if not sliced:
        # + h sum_i btilde_i (df/dtheta)^T Lambda_i, added whole
        with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
            gstep = _scaled(h, gstep)
        gtheta = _accumulate(gtheta, gstep)
    return lam_n, gtheta


# ---------------------------------------------------------------------------
# Lane-batched step: per-lane accepted grids, exact per lane.
#
# A batch-native adaptive forward pass (``AdaptiveStepper`` on a lane state)
# realizes each lane's OWN accepted step sequence.  That sequence is the
# gradient-defining object of the symplectic adjoint, so the backward pass
# replays each lane's own grid: the reverse scan walks the shared
# (max_steps, B) checkpoint rows, runs one lane-vmapped Algorithm-2 step per
# row, and masks each lane by its own n_accepted — a lane with fewer
# accepted steps carries its lambda unchanged through the rows beyond its
# count.  Theorem 2 then applies per lane, so the batched gradient equals
# the sum of per-lane single-solve gradients to rounding
# (tests/test_batch.py pins it against a Python loop of single solves).
# ---------------------------------------------------------------------------

def symplectic_step_adjoint_lanes(f: VectorField, tab: ButcherTableau,
                                  x_n, t_n, h_n, params, lam_next,
                                  combiner: Optional[StageCombiner] = None):
    """One backward Algorithm-2 step for a batch of lanes at once.

    ``x_n``/``lam_next`` are lane-batched (lane axis 0), ``t_n``/``h_n``
    are (B,).  This is the single-lane ``symplectic_step_adjoint`` with the
    per-lane-scalar pieces (stage recomputation, the Eq. (7) Lambda rows,
    one VJP per stage) run under ``jax.vmap`` — NOT a vmap of the whole
    step: ``lax.optimization_barrier`` has no batching rule, so the
    scheduling barrier is applied directly to the lane-batched stage state
    between the vmapped pieces.  The memory discipline is unchanged: one
    stage's (batched) VJP residuals are live at a time.

    Returns (lambda_n, grad_theta_step) with grad_theta_step PER LANE —
    leaves (B,) + param shape — so the caller can mask invalid lanes
    before reducing over the batch.  Lanes may read different slices of a
    ``SlicedField``'s parameters, so this step always takes the dense
    path: each stage's VJP is with respect to the whole ``params``.
    """
    combiner = combiner or get_combiner(tab)
    s = tab.s
    b, c = tab.b, tab.c
    # --- Alg.2 lines 3-7: recompute stages from the per-lane checkpoints --
    Xs, _K = jax.vmap(
        lambda x_l, t_l, h_l: rk_stages(f, tab, x_l, t_l, h_l, params,
                                        combiner))(x_n, t_n, h_n)
    # the stacked adjoint-slope buffer keeps its stage axis LEADING, so the
    # lane axis of every leaf sits at axis 1 (vmap in_axes=1 below).
    L = alloc_stages(s, lam_next)
    lambda_stage_lanes = [
        jax.vmap(lambda lam_l, L_l, h_l, i=i: combiner.lambda_stage(
            lam_l, L_l, h_l, i), in_axes=(0, 1, 0)) for i in range(s)]
    gtheta = None
    dep = lam_next
    for i in reversed(range(s)):
        Lam_i = lambda_stage_lanes[i](lam_next, L, h_n)
        Xi = _barrier_with(Xs[i], dep)  # Xs: list of s lane-batched pytrees

        def stage_vjp(X_l, t_l, Lam_l):
            _, vjp_fn = jax.vjp(lambda X, th: f(X, t_l, th), X_l, params)
            return vjp_fn(Lam_l)

        xbar, thbar = jax.vmap(stage_vjp)(Xi, t_n + c[i] * h_n, Lam_i)
        l_i = jax.tree_util.tree_map(jnp.negative, xbar)
        L = set_stage(L, i, l_i)
        with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
            if b[i] == 0.0:  # Eq. (8): btilde_i = h_n, per lane
                contrib = jax.tree_util.tree_map(
                    lambda g: lane_bcast(h_n, g).astype(g.dtype) * g, thbar)
            else:
                contrib = jax.tree_util.tree_map(
                    lambda g: jnp.asarray(b[i], dtype=g.dtype) * g, thbar)
            gtheta = contrib if gtheta is None else _tree_add(gtheta,
                                                              contrib)
        dep = l_i
    lam_n = jax.vmap(combiner.lambda_update,
                     in_axes=(0, 1, 0))(lam_next, L, h_n)
    with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
        gtheta = jax.tree_util.tree_map(
            lambda g: lane_bcast(h_n, g).astype(g.dtype) * g, gtheta)
    return lam_n, gtheta


# ---------------------------------------------------------------------------
# The backward pass: Algorithm 2 from the checkpoints alone.
# ---------------------------------------------------------------------------

def _replay_segment(f, tab, combiner, params, ckpt, lam, gtheta):
    """One reverse Algorithm-2 scan over one segment's checkpoints.

    ``(xs, ts, h)`` is a fixed grid: every row is a step (a lane batch on
    a fixed grid is one state, never masked).  ``(xs, ts, hs,
    n_accepted)`` is an adaptive grid: rows at or past ``n_accepted`` are
    scratch and leave the carry untouched; a (B,) ``n_accepted`` is a lane
    batch, each lane masked by its own count, and rows past every lane's
    count skip the stage recomputation (``lax.cond`` on ``any(valid)``).
    """
    if len(ckpt) == 3:
        xs, ts, h = ckpt

        def body(carry, inputs):
            lam, gtheta = carry
            x_n, t_n = inputs
            return symplectic_step_adjoint(f, tab, x_n, t_n, h, params, lam,
                                           gtheta, combiner), None

        rows = (xs, ts)
    elif jnp.ndim(ckpt[3]) == 0:
        xs, ts, hs, n_acc = ckpt

        def body(carry, inputs):
            lam, gtheta = carry
            x_n, t_n, h_n, idx = inputs
            valid = idx < n_acc

            def live(_):
                return symplectic_step_adjoint(
                    f, tab, x_n, t_n, h_n, params, lam, gtheta, combiner)

            def dead(_):
                return lam, gtheta

            return jax.lax.cond(valid, live, dead, None), None

        rows = (xs, ts, hs, jnp.arange(ts.shape[0]))
    else:
        xs, ts, hs, n_acc = ckpt

        def body(carry, inputs):
            x_n, t_n, h_n, idx = inputs
            valid = idx < n_acc

            def live(args):
                lam, gtheta = args
                lam2, gstep = symplectic_step_adjoint_lanes(
                    f, tab, x_n, t_n, h_n, params, lam, combiner)
                lam = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(lane_bcast(valid, a), b, a),
                    lam, lam2)
                with jax.named_scope(scopes.ADJOINT_ACCUMULATE):
                    gsum = jax.tree_util.tree_map(
                        lambda g: jnp.sum(jnp.where(
                            lane_bcast(valid, g), g,
                            jnp.zeros((), g.dtype)), axis=0), gstep)
                return lam, _accumulate(gtheta, gsum)

            def dead(args):
                return args

            return jax.lax.cond(jnp.any(valid), live, dead, carry), None

        rows = (xs, ts, hs, jnp.arange(ts.shape[0]))
    (lam, gtheta), _ = jax.lax.scan(body, (lam, gtheta), rows, reverse=True)
    return lam, gtheta


def symplectic_replay(f: VectorField, tab: ButcherTableau, ckpt, params,
                      obs_bar, *, segmented: bool = False,
                      combine_backend: str = "auto"):
    """Algorithm 2: the exact gradient from Algorithm 1's checkpoints.

    ``ckpt`` is ``(xs, ts, h)`` of a fixed grid or ``(xs, ts, hs,
    n_accepted)`` of an adaptive one (lane-batched when ``n_accepted`` is
    (B,)), as a stepper's solution holds them.  ``obs_bar`` is the
    cotangent of the observation: of the final state, or with
    ``segmented`` of the stacked observations at ``ts``, in which case
    every ``ckpt`` leaf carries a leading segment axis.  The segments are
    walked in reverse: observation i's cotangent is added into lambda at
    its segment boundary, then that segment's reverse scan runs; Theorem 2
    holds per segment, so the gradient of any loss over the observations is
    exact to rounding.  Returns ``(lambda_0, grad_theta)``.
    """
    combiner = get_combiner(tab, combine_backend)
    if not segmented:
        return _replay_segment(f, tab, combiner, params, ckpt, obs_bar,
                               _tree_zeros(params))

    def seg_body(carry, seg):
        lam, gtheta = carry
        ob_i, seg_ckpt = seg
        lam = _tree_add(lam, ob_i)
        return _replay_segment(f, tab, combiner, params, seg_ckpt, lam,
                               gtheta), None

    lam0 = jax.tree_util.tree_map(lambda l: jnp.zeros_like(l[0]), obs_bar)
    (lam, gtheta), _ = jax.lax.scan(seg_body, (lam0, _tree_zeros(params)),
                                    (obs_bar, ckpt), reverse=True)
    return lam, gtheta


# ---------------------------------------------------------------------------
# The driver: one custom_vjp for every (stepping, observation, lanes) cell.
#
# Its scalar times cross the custom_vjp boundary as (1,)-shaped arrays (see
# rk.time_lift); ``symplectic_solve`` lifts them.  ``t1`` runs unscanned: the
# single segment's solve and replay are called directly, with no segment
# axis.  ``ts`` runs the segments inside one lax.scan each way, so trace
# size, jaxpr size and compile time are O(1) in len(ts) (docs/adaptive.md).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _symplectic_r1(f, tab, stepping, combine_backend, lanes, segmented,
                   x0, t0r, tobs, params):
    return _symplectic_fwd(f, tab, stepping, combine_backend, lanes,
                           segmented, x0, t0r, tobs, params)[0]


def _symplectic_fwd(f, tab, stepping, combine_backend, lanes, segmented,
                    x0, t0r, tobs, params):
    adaptive = isinstance(stepping, AdaptiveConfig)
    if segmented:
        obs, sol = rk_solve_segments(f, tab, x0, _unlift(t0r), tobs, params,
                                     stepping, combine_backend, lanes=lanes)
    else:
        sol = rk_solve_segment(f, tab, x0, _unlift(t0r), _unlift(tobs),
                               params, stepping, combine_backend,
                               lanes=lanes)
        obs = sol.x_final
        if adaptive:
            obs = apply_on_failure(obs, sol.succeeded, stepping.on_failure)
    # Residuals = Algorithm 1's checkpoints (plus the primal times, kept
    # only so the backward pass can emit dtype-matched zero cotangents).
    ckpt = ((sol.xs, sol.ts, sol.hs, sol.n_accepted) if adaptive
            else (sol.xs, sol.ts, sol.h))
    return obs, (ckpt, params, t0r, tobs)


def _symplectic_bwd(f, tab, stepping, combine_backend, lanes, segmented,
                    res, obs_bar):
    ckpt, params, t0r, tobs = res
    lam0, gtheta = symplectic_replay(f, tab, ckpt, params, obs_bar,
                                     segmented=segmented,
                                     combine_backend=combine_backend)
    return (lam0, _time_zero(t0r), _time_zero(tobs), gtheta)


_symplectic_r1.defvjp(_symplectic_fwd, _symplectic_bwd)


def symplectic_solve(f: VectorField, tab: ButcherTableau, stepping,
                     combine_backend: str, x0, t0, tobs, params, *,
                     lanes: bool = False, segmented: bool = False):
    """Solve with the exact symplectic-adjoint gradient.

    ``stepping`` is an ``int`` (N equal steps per segment) or an
    ``AdaptiveConfig`` (``max_steps`` per segment; failures follow its
    ``on_failure``).  ``tobs`` is the end time ``t1`` (the final state is
    returned), or with ``segmented`` the observation times ``ts`` (the
    states at ``ts`` are returned stacked, the adaptive controller step
    threading across the boundaries).  ``lanes`` declares lane axis 0 of
    every state leaf a batch of independent trajectories: an adaptive
    solve then runs one controller per lane and the replay masks each lane
    by its own grid; ``t1`` may then be (B,), one horizon per lane.
    """
    return _symplectic_r1(f, tab, stepping, combine_backend, lanes,
                          segmented, x0, _lift(t0), _lift(tobs), params)
