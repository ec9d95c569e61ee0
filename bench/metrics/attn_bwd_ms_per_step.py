"""Device time of the attention backward per training step: leaf ops in
the backward of the ``attention`` scope (``kernels/ops.py: attention``),
the XLA VJP behind the Pallas forward's custom VJP.  The forward a replay
recomputes is not counted here (``attn_fwd_ms_per_step`` holds it)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "attn_bwd")
