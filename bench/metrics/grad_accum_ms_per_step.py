"""Device time per training step of the symplectic adjoint's sums of the
parameter gradient over stages and steps: leaf ops under the
``adjoint_accumulate`` scope (``core/symplectic.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "grad_accum")
