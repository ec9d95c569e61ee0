"""Share of the roofline the flash-attention forward reaches: the causal
pairs' flops (2 * head_dim each for q k^T and for p v) and the bytes of q,
k, v and the output at the logical shapes; compute bounds it at these
sizes."""
from bench import kernels


def read(ctx):
    work = getattr(ctx.job, "attention_work", None)
    if work is None:
        return None
    w = work()
    return kernels.roofline_pct(ctx, "attn_fwd", lambda name: w)
