"""Device time of the stage-combine kernels (``core/combine.py`` ->
``kernels/butcher_combine.py``) per training step, from the trace."""
from bench import kernels


def read(ctx):
    s = kernels.op_seconds_per_step(ctx, "combine")
    return None if s is None else 1000.0 * s
