"""Device time of the forward depth or ODE solve per training step: leaf
ops under the program's ``ode_solve`` scope (``core/api.py: solve``) and
not in its backward (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "solve_fwd")
