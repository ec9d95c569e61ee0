"""The comparison that decides ``correct`` for a training cell.

Both sides, the program and the plain reference, report the same readings
of the first steps, taken from the same seed:

* ``loss``   — the loss of each step;
* ``grad``   — per leaf, the norm of the first gradient as the optimizer
  gets it (the program's is worked out from its optimizer state after one
  step);
* ``update`` — per leaf, the norm of the parameters' change after the
  steps.

Each number is the worst over steps or leaves.  A leaf's gap is the gap
between the two norms, not the norm of their difference, measured against
the reference's norm of that leaf or of the median leaf, whichever is
larger, since some gradients are all but zero.  Leaves whose reference
gradient is under a thousandth of the median leaf's move under Adam by
round-off alone; they are left out of ``update``.
"""
from __future__ import annotations

import math
import statistics

TINY_GRAD = 1e-3   # of the median leaf's reference gradient norm


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else (0.0 if a == b else math.inf)


def leaf_gap(prog: dict, ref: dict, leaves=None) -> tuple:
    """(worst gap, its leaf) over ``leaves`` (default: all of ``ref``)."""
    if set(prog) != set(ref):
        missing = sorted(set(prog) ^ set(ref))[:5]
        return math.inf, f"leaf sets differ: {missing}"
    floor = statistics.median(ref.values())
    gaps = {}
    for k in (ref if leaves is None else leaves):
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        gaps[k] = gap if math.isfinite(gap) else math.inf
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def moving_leaves(ref_grad: dict) -> list:
    floor = TINY_GRAD * statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= floor]


def compare_training(prog: dict, ref: dict, limits: dict) -> dict:
    """``{name: {"value", "limit", "where"}}`` for loss, grad and update."""
    loss_gap = max(rel_gap(a, b) if math.isfinite(a) else math.inf
                   for a, b in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        loss_gap = math.inf
    grad_gap, grad_at = leaf_gap(prog["grad"], ref["grad"])
    upd_gap, upd_at = leaf_gap(prog["update"], ref["update"],
                               moving_leaves(ref["grad"]))
    return {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"],
                     "where": "worst step"},
        "grad_gap": {"value": grad_gap, "limit": limits["grad_gap"],
                     "where": grad_at},
        "update_gap": {"value": upd_gap, "limit": limits["update_gap"],
                       "where": upd_at},
    }
