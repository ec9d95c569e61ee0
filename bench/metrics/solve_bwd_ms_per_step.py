"""Device time of the solve's backward per training step: leaf ops in the
backward of the ``ode_solve`` scope, that is the gradient strategy's work
(the symplectic replay or remat's recompute, the field VJPs and the
gradient's accumulation)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "solve_bwd")
