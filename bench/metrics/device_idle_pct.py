"""Share of the traced window in which no operation ran on the device:
1 - (union of op intervals / window), averaged over the chips."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(ctx.trace)
                    / trace.window_seconds(ctx.trace))
