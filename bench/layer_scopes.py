"""Device time under one of the program's layer scopes, both directions.

The DeepSeek-V2 cell names three parts of its step beyond the phases that
``bench/scopes.py`` splits: the dense layers before the depth solve, the
expert layer's routing, and its grouped expert matmuls.  Each reader sums
the leaf ops of the traced window (``trace.window``, ``trace.leaf_ops``)
whose op name lies under the scope (``scopes.under``), mapped from the
compiled step by ``scopes.op_names``, as ``scopes.split`` does.

The TPU compiler expands ``jax.lax.ragged_dot`` into instructions named
``ragged-dot-*`` whose op name is their own, without the name stack (on a
v5e all twelve grouped matmuls of the step, forward, replay and backward,
read ``op_name="ragged-dot-none"``).  In these programs the only ragged
dots are the held experts' grouped matmuls, so an instruction of that name
outside every scope counts under ``moe_experts``.

``unscoped_pct`` is the share of the leaf-op time under none of the
phases' scopes (``ode_solve``, ``lm_loss``, ``optimizer``) nor
``prefix_layers``, or on ops the map lacks: ``bench/scopes.py:
unscoped_pct`` with the prefix layers counted as a phase of their own,
and the ragged dots above counted where they run, inside the solve.

The scope names are spelled out here, not imported from the program, so
that the yardstick does not move with the program.
"""
from __future__ import annotations

from bench import scopes, trace

PREFIX_LAYERS = "prefix_layers"
MOE_ROUTE = "moe_route"
MOE_EXPERTS = "moe_experts"
NAMES = (PREFIX_LAYERS, MOE_ROUTE, MOE_EXPERTS)
RAGGED_DOT = "ragged-dot"
# the scopes that place an op in a part of the step
PHASES = (scopes.ODE_SOLVE, scopes.LM_LOSS, scopes.OPTIMIZER, PREFIX_LAYERS)

_MEMO = []      # [(trace, seconds per step by scope)] of the run


def seconds_per_step(ctx):
    """{scope: seconds a step, or None where no op of the compiled step
    lies under it}, with ``total`` (every leaf op of the window) and
    ``unscoped`` (under none of ``PHASES`` nor any of ``NAMES``, or not
    in the map; None where no op lies under any phase); None without a
    trace or an op-name map."""
    if ctx.trace is None:
        return None
    if _MEMO and _MEMO[0][0] is ctx.trace:
        return _MEMO[0][1]
    names = scopes.op_names(ctx)
    out = None
    if names is not None:
        ops = set(names.values())
        by_op = {op: {s for s in NAMES if op and scopes.under(op, s)}
                 for op in ops}
        placed = {op for op in ops
                  if op and any(scopes.under(op, s) for s in PHASES)}

        def scopes_of(name):
            op = names.get(name)
            if op not in placed and name.startswith(RAGGED_DOT):
                return {MOE_EXPERTS}
            return by_op.get(op, set())

        present = set().union(*map(scopes_of, names))
        lo, hi = trace.window(ctx.trace)
        ns = dict.fromkeys(NAMES + ("total", "unscoped"), 0)
        for ev in ctx.trace.devices.values():
            for name, s, d in trace.leaf_ops(ev):
                if s < lo or s + d > hi:
                    continue
                ns["total"] += d
                inside = scopes_of(name)
                if names.get(name) not in placed and not inside:
                    ns["unscoped"] += d
                for scope in inside:
                    ns[scope] += d
        per = 1e-9 / max(len(ctx.trace.devices), 1) / ctx.trace_steps
        out = {s: ns[s] * per if s in present else None for s in NAMES}
        out["total"] = ns["total"] * per
        out["unscoped"] = ns["unscoped"] * per if placed else None
    _MEMO[:] = [(ctx.trace, out)]
    return out


def ms_per_step(ctx, scope: str):
    sec = seconds_per_step(ctx)
    if sec is None or sec[scope] is None:
        return None
    return 1000.0 * sec[scope]


def unscoped_pct(ctx):
    """Share of the leaf-op time under none of ``PHASES``; None where the
    program has none of them."""
    sec = seconds_per_step(ctx)
    if sec is None or sec["unscoped"] is None or sec["total"] <= 0:
        return None
    return 100.0 * sec["unscoped"] / sec["total"]
