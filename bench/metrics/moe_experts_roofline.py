"""Share of the roofline the held experts' grouped matmuls reach over a
training step: the least time of the step's expert work
(``job.moe_experts_work()``: the three projections over the tokens the
program's router sent to each held expert on the last batch, the held
experts' weights and the rows in and out; the forward twice a step under
the symplectic replay, the backward once at twice the forward's work) over
the device time under the ``moe_experts`` scope."""
from bench import counts, layer_scopes


def read(ctx):
    work = getattr(ctx.job, "moe_experts_work", None)
    ms = layer_scopes.ms_per_step(ctx, layer_scopes.MOE_EXPERTS)
    if work is None or not ms:
        return None
    flops, bytes_moved = work()
    least, _ = counts.roofline(flops, bytes_moved,
                               ctx.peaks["bf16_flops_per_s"],
                               ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1000.0)
