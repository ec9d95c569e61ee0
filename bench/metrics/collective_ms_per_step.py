"""Device time of the collectives per training step, mean over chips: the
ops of the traced window named for an all-reduce, all-gather,
reduce-scatter, collective-permute or all-to-all (an HLO instruction keeps
its opcode's name, with ``-start`` and ``-done`` on the halves of an
asynchronous one)."""
from bench import trace

PATTERNS = ("all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all")


def _collective(name: str) -> bool:
    return name.startswith(PATTERNS)


def read(ctx):
    if ctx.trace is None or not trace.op_events(ctx.trace, _collective):
        return None
    return 1000.0 * trace.op_seconds(ctx.trace, _collective) \
        / ctx.trace_steps
