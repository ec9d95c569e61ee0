"""Device time of the Pallas flash-attention forward kernel
(``kernels/flash_attention.py`` via ``kernels/ops.py``) per training
step, in the forward solve and wherever a gradient strategy replays it."""
from bench import kernels


def read(ctx):
    s = kernels.op_seconds_per_step(ctx, "attn_fwd")
    return None if s is None else 1000.0 * s
