"""Core neural-ODE library: tableaus, RK solvers, and the symplectic adjoint.

Public API (composable, core/api.py):
    solve, Solution, SaveAt, GradientStrategy, SymplecticAdjoint,
    DirectBackprop, RematStep, RematSolve, ContinuousAdjoint,
    register_gradient, as_gradient, GRADIENT_REGISTRY, capability_matrix,
    AdaptiveConfig, get_tableau, ButcherTableau,
    COMBINE_BACKENDS, StageCombiner, get_combiner

Legacy front-ends (deprecated shims, core/odeint.py):
    odeint, odeint_with_stats, GRAD_MODES, TS_MODES
"""
from .combine import (COMBINE_BACKENDS, StageCombiner, alloc_stages,
                      get_combiner, set_stage, stage_prefix, stage_suffix)
from .api import (GRADIENT_REGISTRY, STEPPING_KINDS, SAVEAT_KINDS,
                  ContinuousAdjoint, DirectBackprop, GradientStrategy,
                  RematSolve, RematStep, SaveAt, Solution, SymplecticAdjoint,
                  as_gradient, batched_capability_matrix, capability_matrix,
                  mesh_capability_matrix, register_gradient, solve)
from .odeint import GRAD_MODES, TS_MODES, odeint, odeint_with_stats
from .rk import (ON_FAILURE_POLICIES, AdaptiveConfig, AdaptiveSolution,
                 BatchedAdaptiveSolution, SlicedField, apply_on_failure,
                 apply_on_failure_lanes, hermite_observe, lane_count,
                 rk_solve_adaptive, rk_solve_adaptive_batched,
                 rk_solve_fixed, rk_solve_segment, rk_solve_segments,
                 rk_stages, rk_step, tree_scale_add)
from .stepper import (AdaptiveStepper, FixedSolverState, FixedStepper,
                      SolverState)
from .symplectic import (symplectic_replay, symplectic_solve,
                         symplectic_step_adjoint,
                         symplectic_step_adjoint_lanes)
from .adjoint import (odeint_adjoint, odeint_adjoint_adaptive,
                      odeint_adjoint_adaptive_batched)
from .backprop import odeint_backprop, odeint_remat_solve, odeint_remat_step
from .tableau import HERMITE_DENSE_W, TABLEAUS, ButcherTableau, get_tableau

__all__ = [
    "solve", "Solution", "SaveAt", "GradientStrategy", "SymplecticAdjoint",
    "DirectBackprop", "RematStep", "RematSolve", "ContinuousAdjoint",
    "register_gradient", "as_gradient", "GRADIENT_REGISTRY",
    "capability_matrix", "batched_capability_matrix",
    "mesh_capability_matrix",
    "STEPPING_KINDS", "SAVEAT_KINDS",
    "odeint", "odeint_with_stats", "GRAD_MODES", "TS_MODES",
    "AdaptiveConfig", "AdaptiveSolution", "BatchedAdaptiveSolution",
    "ON_FAILURE_POLICIES",
    "COMBINE_BACKENDS", "StageCombiner", "get_combiner", "alloc_stages",
    "set_stage", "stage_prefix", "stage_suffix",
    "rk_solve_fixed", "rk_solve_adaptive", "rk_solve_adaptive_batched",
    "rk_solve_segment", "rk_solve_segments", "lane_count",
    "rk_step", "rk_stages", "tree_scale_add", "apply_on_failure",
    "SlicedField",
    "apply_on_failure_lanes",
    "SolverState", "FixedSolverState", "AdaptiveStepper", "FixedStepper",
    "hermite_observe", "symplectic_solve", "symplectic_replay",
    "symplectic_step_adjoint", "symplectic_step_adjoint_lanes",
    "odeint_adjoint", "odeint_adjoint_adaptive",
    "odeint_adjoint_adaptive_batched",
    "odeint_backprop", "odeint_remat_step", "odeint_remat_solve",
    "TABLEAUS", "ButcherTableau", "get_tableau", "HERMITE_DENSE_W",
]
