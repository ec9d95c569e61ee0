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
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..runtime import scopes
from .combine import StageCombiner, alloc_stages, get_combiner, set_stage
from .rk import (AdaptiveConfig, SlicedField, VectorField, apply_on_failure,
                 apply_on_failure_lanes, lane_bcast, rk_solve_adaptive,
                 rk_solve_adaptive_batched,
                 rk_solve_adaptive_batched_saveat_stacked,
                 rk_solve_adaptive_saveat_stacked, rk_solve_fixed, rk_stages,
                 segment_starts, take_slice, time_lift as _lift,
                 time_unlift as _unlift,
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
# Fixed-grid driver
# ---------------------------------------------------------------------------

# All custom_vjp drivers below take their scalar times as (1,)-shaped
# arrays (see rk.time_lift); the public odeint_* wrappers keep the scalar
# signature and lift at the boundary.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_r1(f: VectorField, tab: ButcherTableau, n_steps: int,
                          combine_backend: str, x0, t0r, t1r, params):
    sol = rk_solve_fixed(f, tab, x0, _unlift(t0r), _unlift(t1r), n_steps,
                         params,
                         combine_backend)
    return sol.x_final


def odeint_symplectic(f: VectorField, tab: ButcherTableau, n_steps: int,
                      combine_backend: str, x0, t0, t1, params):
    return _odeint_symplectic_r1(f, tab, n_steps, combine_backend,
                                 x0, _lift(t0), _lift(t1), params)


def _sym_fwd(f, tab, n_steps, combine_backend, x0, t0r, t1r, params):
    sol = rk_solve_fixed(f, tab, x0, _unlift(t0r), _unlift(t1r), n_steps,
                         params,
                         combine_backend)
    # Residuals = Algorithm 1's checkpoints (plus the primal times, kept
    # only so the backward pass can emit dtype-matched zero cotangents).
    return sol.x_final, (sol.xs, sol.ts, sol.h, params, t0r, t1r)


def _sym_bwd(f, tab, n_steps, combine_backend, res, lam_N):
    xs, ts, h, params, t0, t1 = res
    combiner = get_combiner(tab, combine_backend)

    def body(carry, inputs):
        lam, gtheta = carry
        x_n, t_n = inputs
        return symplectic_step_adjoint(f, tab, x_n, t_n, h, params, lam,
                                       gtheta, combiner), None

    (lam0, gtheta), _ = jax.lax.scan(body, (lam_N, _tree_zeros(params)),
                                     (xs, ts), reverse=True)
    return (lam0, _time_zero(t0), _time_zero(t1), gtheta)


_odeint_symplectic_r1.defvjp(_sym_fwd, _sym_bwd)


# ---------------------------------------------------------------------------
# Adaptive driver (bounded checkpoint buffer, masked reverse scan)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_adaptive_r1(f: VectorField, tab: ButcherTableau,
                                   cfg: AdaptiveConfig, combine_backend: str,
                                   x0, t0r, t1r, params):
    sol = rk_solve_adaptive(f, tab, x0, _unlift(t0r), _unlift(t1r), params,
                            cfg,
                            combine_backend)
    return apply_on_failure(sol.x_final, sol.succeeded, cfg.on_failure)


def odeint_symplectic_adaptive(f: VectorField, tab: ButcherTableau,
                               cfg: AdaptiveConfig, combine_backend: str,
                               x0, t0, t1, params):
    return _odeint_symplectic_adaptive_r1(f, tab, cfg, combine_backend,
                                          x0, _lift(t0), _lift(t1), params)


def _syma_fwd(f, tab, cfg, combine_backend, x0, t0r, t1r, params):
    sol = rk_solve_adaptive(f, tab, x0, _unlift(t0r), _unlift(t1r), params,
                            cfg,
                            combine_backend)
    res = (sol.xs, sol.ts, sol.hs, sol.n_accepted, params, t0r, t1r)
    x_final = apply_on_failure(sol.x_final, sol.succeeded, cfg.on_failure)
    return x_final, res


def _syma_bwd(f, tab, cfg, combine_backend, res, lam_N):
    xs, ts, hs, n_acc, params, t0, t1 = res
    combiner = get_combiner(tab, combine_backend)

    def body(carry, inputs):
        lam, gtheta = carry
        x_n, t_n, h_n, idx = inputs
        valid = idx < n_acc

        def live(_):
            return symplectic_step_adjoint(
                f, tab, x_n, t_n, h_n, params, lam, gtheta, combiner)

        def dead(_):
            return lam, gtheta

        lam, gtheta = jax.lax.cond(valid, live, dead, None)
        return (lam, gtheta), None

    idxs = jnp.arange(cfg.max_steps)
    (lam0, gtheta), _ = jax.lax.scan(
        body, (lam_N, _tree_zeros(params)), (xs, ts, hs, idxs),
        reverse=True)
    return (lam0, _time_zero(t0), _time_zero(t1), gtheta)


_odeint_symplectic_adaptive_r1.defvjp(_syma_fwd, _syma_bwd)


# ---------------------------------------------------------------------------
# SaveAt drivers: observation at user times ts, exact gradient preserved.
#
# The solve is split into checkpointed segments at the observation times
# (each observation is a segment endpoint, so no interpolation enters the
# differentiated map).  The backward pass walks the segments in reverse;
# each segment is the existing Algorithm 2 scan, and the incoming cotangent
# of observation i is injected into lambda at its segment boundary before
# that segment's scan runs.  Theorem 2 then applies per segment, so the
# full gradient of any loss over the observations is exact to rounding.
#
# Both directions are lax.scans OVER THE SEGMENTS (segments share n_steps /
# max_steps, so shapes are uniform): the forward stacks per-segment
# checkpoint buffers as scan outputs, the backward is a reverse scan whose
# body injects the i-th observation cotangent (an indexed read from the
# stacked obs_bar via the scan's own slicing) and then runs the per-segment
# Algorithm 2 scan.  Trace size, jaxpr size, and compile time are O(1) in
# the number of observations — see docs/adaptive.md.
# ---------------------------------------------------------------------------

def _sym_saveat_solve(f, tab, n_steps, combine_backend, x0, t0r, ts, params):
    """Forward segmented fixed-grid solve; returns (obs, residuals)."""

    def body(x, seg):
        a, b = seg
        sol = rk_solve_fixed(f, tab, x, a, b, n_steps, params,
                             combine_backend)
        return sol.x_final, (sol.x_final, sol.xs, sol.ts, sol.h)

    _, (obs, seg_xs, seg_ts, seg_hs) = jax.lax.scan(
        body, x0, (segment_starts(_unlift(t0r), ts), ts))
    return obs, (seg_xs, seg_ts, seg_hs, params, t0r, ts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_saveat_r1(f: VectorField, tab: ButcherTableau,
                                 n_steps: int, combine_backend: str,
                                 x0, t0r, ts, params):
    obs, _ = _sym_saveat_solve(f, tab, n_steps, combine_backend,
                               x0, t0r, ts, params)
    return obs


def odeint_symplectic_saveat(f: VectorField, tab: ButcherTableau,
                             n_steps: int, combine_backend: str,
                             x0, t0, ts, params):
    """Fixed-grid solve observed at ts (n_steps per segment).

    Returns the solution stacked over the observation times (leading dim
    len(ts) per leaf).
    """
    return _odeint_symplectic_saveat_r1(f, tab, n_steps, combine_backend,
                                        x0, _lift(t0), ts, params)


def _sym_saveat_fwd(f, tab, n_steps, combine_backend, x0, t0r, ts, params):
    return _sym_saveat_solve(f, tab, n_steps, combine_backend,
                             x0, t0r, ts, params)


def _sym_saveat_bwd(f, tab, n_steps, combine_backend, res, obs_bar):
    xs_all, ts_all, hs_all, params, t0, ts = res
    combiner = get_combiner(tab, combine_backend)
    lam0 = jax.tree_util.tree_map(lambda l: jnp.zeros_like(l[0]), obs_bar)

    def seg_body(carry, seg):
        lam, gtheta = carry
        ob_i, seg_xs, seg_ts, h_seg = seg
        # inject the cotangent arriving at this segment boundary
        lam = _tree_add(lam, ob_i)

        def body(carry_c, inputs):
            lam_c, g_c = carry_c
            x_n, t_n = inputs
            return symplectic_step_adjoint(
                f, tab, x_n, t_n, h_seg, params, lam_c, g_c, combiner), None

        (lam, gtheta), _ = jax.lax.scan(body, (lam, gtheta),
                                        (seg_xs, seg_ts), reverse=True)
        return (lam, gtheta), None

    (lam, gtheta), _ = jax.lax.scan(
        seg_body, (lam0, _tree_zeros(params)),
        (obs_bar, xs_all, ts_all, hs_all), reverse=True)
    return (lam, _time_zero(t0), _time_zero(ts), gtheta)


_odeint_symplectic_saveat_r1.defvjp(_sym_saveat_fwd, _sym_saveat_bwd)


def _syma_saveat_solve(f, tab, cfg, combine_backend, x0, t0r, ts, params):
    obs, sols = rk_solve_adaptive_saveat_stacked(
        f, tab, x0, _unlift(t0r), ts, params, cfg, combine_backend)
    res = (sols.xs, sols.ts, sols.hs, sols.n_accepted, params, t0r, ts)
    return obs, res


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_saveat_adaptive_r1(f: VectorField,
                                          tab: ButcherTableau,
                                          cfg: AdaptiveConfig,
                                          combine_backend: str,
                                          x0, t0r, ts, params):
    obs, _ = _syma_saveat_solve(f, tab, cfg, combine_backend,
                                x0, t0r, ts, params)
    return obs


def odeint_symplectic_saveat_adaptive(f: VectorField, tab: ButcherTableau,
                                      cfg: AdaptiveConfig,
                                      combine_backend: str,
                                      x0, t0, ts, params):
    """Adaptive solve observed at ts (one adaptive segment per interval).

    The controller threads its unclamped step across segment boundaries
    (rk_solve_adaptive_saveat), so observation times cost one clamped
    landing step each instead of a collapsed restart.  Failed segments
    follow cfg.on_failure.
    """
    return _odeint_symplectic_saveat_adaptive_r1(
        f, tab, cfg, combine_backend, x0, _lift(t0), ts, params)


def _syma_saveat_fwd(f, tab, cfg, combine_backend, x0, t0r, ts, params):
    return _syma_saveat_solve(f, tab, cfg, combine_backend,
                              x0, t0r, ts, params)


def _syma_saveat_bwd(f, tab, cfg, combine_backend, res, obs_bar):
    xs_all, ts_all, hs_all, n_accs, params, t0, ts = res
    combiner = get_combiner(tab, combine_backend)
    lam0 = jax.tree_util.tree_map(lambda l: jnp.zeros_like(l[0]), obs_bar)
    idxs = jnp.arange(cfg.max_steps)

    def seg_body(carry, seg):
        lam, gtheta = carry
        ob_i, seg_xs, seg_ts, seg_hs, n_acc = seg
        lam = _tree_add(lam, ob_i)

        def body(carry_c, inputs):
            lam_c, g_c = carry_c
            x_n, t_n, h_n, idx = inputs
            valid = idx < n_acc

            def live(_):
                return symplectic_step_adjoint(
                    f, tab, x_n, t_n, h_n, params, lam_c, g_c, combiner)

            def dead(_):
                return lam_c, g_c

            out = jax.lax.cond(valid, live, dead, None)
            return out, None

        (lam, gtheta), _ = jax.lax.scan(
            body, (lam, gtheta), (seg_xs, seg_ts, seg_hs, idxs),
            reverse=True)
        return (lam, gtheta), None

    (lam, gtheta), _ = jax.lax.scan(
        seg_body, (lam0, _tree_zeros(params)),
        (obs_bar, xs_all, ts_all, hs_all, n_accs), reverse=True)
    return (lam, _time_zero(t0), _time_zero(ts), gtheta)


_odeint_symplectic_saveat_adaptive_r1.defvjp(_syma_saveat_fwd,
                                             _syma_saveat_bwd)


# ---------------------------------------------------------------------------
# Batch-native adaptive drivers: per-lane accepted grids, exact per lane.
#
# The forward pass is the masked batch-native driver
# (rk_solve_adaptive_batched): each lane realizes ITS OWN accepted step
# sequence.  That sequence is the gradient-defining object of the symplectic
# adjoint, so the backward pass must replay each lane's own grid — the
# reverse scan walks the shared (max_steps, B) checkpoint rows, runs one
# lane-vmapped Algorithm-2 step per row, and masks each lane by its own
# n_accepted: a lane with fewer accepted steps simply carries its lambda
# unchanged through the rows beyond its count.  Theorem 2 then applies per
# lane, so the batched gradient equals the sum of per-lane single-solve
# gradients to rounding (tests/test_batch.py pins it against a Python loop
# of single solves).
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


def _masked_lanes_alg2_scan(f, tab, combiner, params, max_steps,
                            xs, ts, hs, n_acc, lam, gtheta):
    """Reverse Algorithm-2 scan over (max_steps, B) checkpoint rows.

    ``n_acc`` is (B,); rows >= a lane's count leave that lane's lambda and
    its grad-theta contribution untouched.  Rows beyond EVERY lane's count
    skip the stage recomputation entirely (lax.cond on any(valid)).
    """
    def body(carry, inputs):
        lam, gtheta = carry
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
                    lambda g: jnp.sum(jnp.where(lane_bcast(valid, g), g,
                                                jnp.zeros((), g.dtype)),
                                      axis=0), gstep)
            return lam, _accumulate(gtheta, gsum)

        def dead(args):
            return args

        out = jax.lax.cond(jnp.any(valid), live, dead, (lam, gtheta))
        return out, None

    idxs = jnp.arange(max_steps)
    (lam, gtheta), _ = jax.lax.scan(body, (lam, gtheta),
                                    (xs, ts, hs, idxs), reverse=True)
    return lam, gtheta


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_adaptive_batched_r1(f: VectorField,
                                           tab: ButcherTableau,
                                           cfg: AdaptiveConfig,
                                           combine_backend: str,
                                           x0, t0r, t1r, params):
    sol = rk_solve_adaptive_batched(f, tab, x0, _unlift(t0r), _unlift(t1r),
                                    params, cfg,
                                    combine_backend)
    return apply_on_failure_lanes(sol.x_final, sol.succeeded, cfg.on_failure)


def odeint_symplectic_adaptive_batched(f: VectorField, tab: ButcherTableau,
                                       cfg: AdaptiveConfig,
                                       combine_backend: str,
                                       x0, t0, t1, params):
    """Batch-native adaptive solve (lane axis 0) with the exact symplectic
    adjoint replaying each lane's own accepted grid."""
    return _odeint_symplectic_adaptive_batched_r1(
        f, tab, cfg, combine_backend, x0, _lift(t0), _lift(t1), params)


def _symab_fwd(f, tab, cfg, combine_backend, x0, t0r, t1r, params):
    sol = rk_solve_adaptive_batched(f, tab, x0, _unlift(t0r), _unlift(t1r),
                                    params, cfg,
                                    combine_backend)
    res = (sol.xs, sol.ts, sol.hs, sol.n_accepted, params, t0r, t1r)
    x_final = apply_on_failure_lanes(sol.x_final, sol.succeeded,
                                     cfg.on_failure)
    return x_final, res


def _symab_bwd(f, tab, cfg, combine_backend, res, lam_N):
    xs, ts, hs, n_acc, params, t0, t1 = res
    combiner = get_combiner(tab, combine_backend)
    lam0, gtheta = _masked_lanes_alg2_scan(
        f, tab, combiner, params, cfg.max_steps, xs, ts, hs, n_acc,
        lam_N, _tree_zeros(params))
    return (lam0, _time_zero(t0), _time_zero(t1), gtheta)


_odeint_symplectic_adaptive_batched_r1.defvjp(_symab_fwd, _symab_bwd)


def _symab_saveat_solve(f, tab, cfg, combine_backend, x0, t0r, ts, params):
    obs, sols = rk_solve_adaptive_batched_saveat_stacked(
        f, tab, x0, _unlift(t0r), ts, params, cfg, combine_backend)
    res = (sols.xs, sols.ts, sols.hs, sols.n_accepted, params, t0r, ts)
    return obs, res


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _odeint_symplectic_saveat_adaptive_batched_r1(
        f: VectorField, tab: ButcherTableau, cfg: AdaptiveConfig,
        combine_backend: str, x0, t0r, ts, params):
    obs, _ = _symab_saveat_solve(f, tab, cfg, combine_backend,
                                 x0, t0r, ts, params)
    return obs


def odeint_symplectic_saveat_adaptive_batched(
        f: VectorField, tab: ButcherTableau, cfg: AdaptiveConfig,
        combine_backend: str, x0, t0, ts, params):
    """Batch-native adaptive solve observed at the (shared) times ``ts``.

    Per-lane controller state threads across observation boundaries
    (rk_solve_adaptive_batched_saveat_stacked); the backward pass walks the
    segments in reverse, injects the per-lane observation cotangent at each
    boundary, and replays every lane's own accepted grid inside the
    segment.  Exact per lane to rounding.
    """
    return _odeint_symplectic_saveat_adaptive_batched_r1(
        f, tab, cfg, combine_backend, x0, _lift(t0), ts, params)


def _symab_saveat_fwd(f, tab, cfg, combine_backend, x0, t0r, ts, params):
    return _symab_saveat_solve(f, tab, cfg, combine_backend,
                               x0, t0r, ts, params)


def _symab_saveat_bwd(f, tab, cfg, combine_backend, res, obs_bar):
    xs_all, ts_all, hs_all, n_accs, params, t0, ts = res
    combiner = get_combiner(tab, combine_backend)
    lam0 = jax.tree_util.tree_map(lambda l: jnp.zeros_like(l[0]), obs_bar)

    def seg_body(carry, seg):
        lam, gtheta = carry
        ob_i, seg_xs, seg_ts, seg_hs, n_acc = seg
        lam = _tree_add(lam, ob_i)
        lam, gtheta = _masked_lanes_alg2_scan(
            f, tab, combiner, params, cfg.max_steps,
            seg_xs, seg_ts, seg_hs, n_acc, lam, gtheta)
        return (lam, gtheta), None

    (lam, gtheta), _ = jax.lax.scan(
        seg_body, (lam0, _tree_zeros(params)),
        (obs_bar, xs_all, ts_all, hs_all, n_accs), reverse=True)
    return (lam, _time_zero(t0), _time_zero(ts), gtheta)


_odeint_symplectic_saveat_adaptive_batched_r1.defvjp(_symab_saveat_fwd,
                                                     _symab_saveat_bwd)
