"""Kernel times and roofline shares from the device trace.

``kernels.json`` maps op names to layers.  A roofline share counts the work
of the logical shapes (``counts.py``), the same whatever implements the
kernel, over the kernel's measured device time.
"""
from __future__ import annotations

import json
import os

from bench import counts, trace

TABLE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "kernels.json")


def matcher(which: str):
    """Is an op, by name, one of the layer's kernels?"""
    with open(TABLE_FILE) as fh:
        pats = tuple(json.load(fh)[which]["patterns"])
    return lambda name: name.startswith(pats)


def op_seconds_per_step(ctx, which: str):
    """Device seconds of the layer's ops per traced step, mean over chips;
    None where the window ran none."""
    if ctx.trace is None:
        return None
    match = matcher(which)
    if not trace.op_events(ctx.trace, match):
        return None
    return trace.op_seconds(ctx.trace, match) / ctx.trace_steps


def roofline_pct(ctx, which: str, work):
    """Share of the chip's roofline over every ``which`` op of the traced
    window: sum of least times over sum of measured times.  ``work(name)``
    gives an op's (flops, bytes), or None when it cannot be counted, which
    leaves the metric out."""
    if ctx.trace is None:
        return None
    events = trace.op_events(ctx.trace, matcher(which))
    if not events:
        return None
    least = 0.0
    for name, _ in events:
        w = work(name)
        if w is None:
            return None
        least += counts.roofline(w[0], w[1], ctx.peaks["bf16_flops_per_s"],
                                 ctx.peaks["hbm_bytes_per_s"])[0]
    return 100.0 * least / sum(d for _, d in events)
