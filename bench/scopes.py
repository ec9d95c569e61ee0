"""Device time of the training step's phases, read from the program's named
scopes.

The program names its phases with ``jax.named_scope``; the compiler writes
each operation's name stack into ``metadata={op_name="..."}`` of the
compiled HLO.  ``op_names`` compiles the job's timed step once more, at
abstract arguments with the shapes of its state and last batch, and maps
every instruction name to its ``op_name``: the names the device trace gives
its ops (``bench/trace.py``).  Only names are read from the text, never
shapes or placement.

The scope names are spelled out here, not imported from the program, so
that the yardstick does not move with the program.

Direction.  Transforms wrap the name of the scope they were applied under:
an op of a scope's backward carries ``transpose(jvp(<scope>))``, an op of a
forward replayed for a VJP ``jvp(<scope>)``.  A transform applied to a loop
or a rematerialized block wraps the name of that block, and the scopes
inside it stay bare.  So an op lies in the backward of scope ``S`` when the
last direction mark on its name stack, up to and including ``S`` itself, is
a transpose: a component whose outermost AD transform is ``transpose``.
``jvp`` marks a forward, and so does ``rematted_computation``, the name JAX
gives the forward a remat recomputes inside its backward.  Hence the
symplectic replay's recomputed attention forward (``transpose(jvp(
ode_solve))/.../jvp(attention)``) is backward of ``ode_solve`` and forward
of ``attention``, and remat's recompute (``transpose(jvp(ode_solve))/.../
rematted_computation/attention``) likewise.

Time is summed over leaf ops only (``trace.leaf_ops``): a ``while`` op's
event spans its body's ops.  Times are per traced step, averaged over the
devices; an op whose name the map lacks is unscoped.
"""
from __future__ import annotations

import re
import sys

from bench import trace

ODE_SOLVE = "ode_solve"
ADJOINT_ACCUMULATE = "adjoint_accumulate"
ATTENTION = "attention"
LM_LOSS = "lm_loss"
OPTIMIZER = "optimizer"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"(\w+)\((.*)\)")
_AD = ("jvp", "transpose")
_FORWARD_BLOCK = "rematted_computation"


def op_names_of_text(text: str) -> dict:
    """Instruction name -> ``op_name`` of every instruction of compiled HLO
    text; "" for one the compiler made without a name stack."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def _unwrap(component: str) -> tuple:
    """(wrappers outermost first, core name) of one name-stack component:
    ``transpose(jvp(ode_solve))`` -> (["transpose", "jvp"], "ode_solve")."""
    wrappers = []
    m = _WRAPPED.fullmatch(component)
    while m:
        wrappers.append(m.group(1))
        component = m.group(2)
        m = _WRAPPED.fullmatch(component)
    return wrappers, component


def _scope_at(components, scope: str):
    """Index of the outermost component that is the scope ``scope`` (a
    function name under ``jit`` is not a scope), or None."""
    for i, c in enumerate(components):
        wrappers, core = _unwrap(c)
        if core == scope and "jit" not in wrappers and "pjit" not in wrappers:
            return i
    return None


def under(op_name: str, scope: str) -> bool:
    """Does the op lie in scope ``scope``, either direction?"""
    return _scope_at(op_name.split("/"), scope) is not None


def backward(op_name: str, scope: str) -> bool:
    """Does the op lie in the backward of scope ``scope`` (module
    docstring)?"""
    components = op_name.split("/")
    i = _scope_at(components, scope)
    if i is None:
        return False
    mark = None
    for c in components[:i + 1]:
        wrappers, core = _unwrap(c)
        ad = [w for w in wrappers if w in _AD]
        if ad:
            mark = ad[0]
        elif core == _FORWARD_BLOCK:
            mark = "jvp"
    return mark == "transpose"


def phase(op_name) -> str:
    """The disjoint part of the step an op belongs to: ``solve_fwd``,
    ``solve_bwd``, ``loss``, ``optim`` or ``unscoped``."""
    if not op_name:
        return "unscoped"
    if under(op_name, ODE_SOLVE):
        return "solve_bwd" if backward(op_name, ODE_SOLVE) else "solve_fwd"
    if under(op_name, LM_LOSS):
        return "loss"
    if under(op_name, OPTIMIZER):
        return "optim"
    return "unscoped"


def parts(op_name) -> set:
    """Every part of the step an op counts in: its ``phase``, and
    ``grad_accum`` (under ``adjoint_accumulate``) and ``attn_bwd``
    (backward of ``attention``), which cut across the phases."""
    out = {phase(op_name)}
    if op_name:
        if under(op_name, ADJOINT_ACCUMULATE):
            out.add("grad_accum")
        if backward(op_name, ATTENTION):
            out.add("attn_bwd")
    return out


def split(tr: trace.Trace, names: dict, steps: int) -> dict:
    """Seconds per step of each part (``parts``) of the traced window's
    leaf-op time, of all of it (``total``) and of the ops the map holds
    (``mapped``: instructions of the compiled step).  ``present`` holds the parts that any instruction of the
    map belongs to: a part the program has no op of reads None."""
    lo, hi = trace.window(tr)
    by_op = {op: parts(op) for op in set(names.values())}
    ns = dict.fromkeys(("solve_fwd", "solve_bwd", "loss", "optim",
                        "unscoped", "grad_accum", "attn_bwd", "total",
                        "mapped"), 0)
    for ev in tr.devices.values():
        for name, s, d in trace.leaf_ops(ev):
            if s < lo or s + d > hi:
                continue
            op = names.get(name)
            ns["total"] += d
            if op is not None:
                ns["mapped"] += d
            for part in by_op[op] if op is not None else ("unscoped",):
                ns[part] += d
    n_dev = max(len(tr.devices), 1)
    out = {k: v / n_dev / 1e9 / steps for k, v in ns.items()}
    out["present"] = set().union(*by_op.values())
    return out


def _compiled_text(jax, fn, args) -> str:
    text = fn.lower(*args).compile().as_text() or ""
    if "op_name=" in text:
        return text
    # an executable read back from the persistent cache may come without
    # its text: compile this one program past the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return fn.lower(*args).compile().as_text() or ""
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def op_names(ctx):
    """Instruction name -> ``op_name`` of the job's timed step, compiled at
    abstract arguments shaped like its state and last batch; None where
    the job does not expose them."""
    import jax
    job = ctx.job
    fn = getattr(job, "step_fn", None)
    state = getattr(job, "state", None)
    batch = getattr(job, "_batch", None)
    if fn is None or state is None or batch is None:
        return None
    args = (state, *batch) if isinstance(batch, tuple) else (state, batch)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding,
                                       weak_type=x.weak_type), args)
    return op_names_of_text(_compiled_text(jax, fn, abstract))


_MEMO = []      # [(trace, split)] of the run's traced window


def phases(ctx):
    """``split`` of the run's traced window, computed once per run; None
    without a trace or an op-name map."""
    if ctx.trace is None:
        return None
    if _MEMO and _MEMO[0][0] is ctx.trace:
        return _MEMO[0][1]
    names = op_names(ctx)
    out = None if names is None else split(ctx.trace, names,
                                           ctx.trace_steps)
    _MEMO[:] = [(ctx.trace, out)]
    if out is not None and out["total"] > 0:
        whole = sum(out[k] for k in ("solve_fwd", "solve_bwd", "loss",
                                     "optim", "unscoped"))
        print(f"scopes: leaf ops {out['total']!r} s a step, in the map "
              f"{100.0 * out['mapped'] / out['total']!r} %, phases sum "
              f"{whole!r} s", file=sys.stderr)
    return out


def ms_per_step(ctx, part: str):
    """Milliseconds a step of ``part``; None where the program has no op
    of it."""
    p = phases(ctx)
    if p is None or part not in p["present"]:
        return None
    return 1000.0 * p[part]


def unscoped_pct(ctx):
    """Share of the leaf-op time under none of the phases' scopes; None
    where the program has none of them."""
    p = phases(ctx)
    if p is None or p["total"] <= 0 or not p["present"] & {
            "solve_fwd", "solve_bwd", "loss", "optim"}:
        return None
    return 100.0 * p["unscoped"] / p["total"]
