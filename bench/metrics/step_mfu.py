"""The whole training step's share of the chip's peak: the kind's model
FLOPs of one step (``job.flops_per_step()``; recomputation never counts)
over the step time of the untraced window times the chips' peak bf16
FLOP/s.  The LM counts PaLM's 6N + 12LHQT a token; the CNF counts its
solver's attempted and accepted steps on the last batches."""


def read(ctx):
    flops = getattr(ctx.job, "flops_per_step", None)
    if flops is None:
        return None
    step_s = ctx.window_s / ctx.steps
    peak = ctx.peaks["bf16_flops_per_s"] * len(ctx.devices)
    return 100.0 * flops() / (step_s * peak)
