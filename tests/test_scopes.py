"""The training step's phases carry their named scopes into the compiled
HLO (``repro.runtime.scopes``): every matmul and kernel lies in a phase,
each phase has ops, the symplectic adjoint's gradient sums are named, and
the direction the benchmark reads from a name stack (``bench/scopes.py``)
holds on the programs as compiled.  Smoke sizes on the CPU, Pallas in
interpret mode."""
import functools
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes as bscopes  # noqa: E402
from repro.configs import get_smoke_arch  # noqa: E402
from repro.configs.base import NodeConfig  # noqa: E402
from repro.runtime import scopes  # noqa: E402

CASES = ("symplectic", "remat_step", "cnf")
LM_CASES = ("symplectic", "remat_step")
HEAVY = ("dot", "custom-call", "convolution")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\s([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _lm_step_text(grad_mode: str) -> str:
    from repro.train import TrainConfig, init_train_state, make_train_step
    arch = get_smoke_arch("qwen3-0.6b").with_(
        use_pallas=True, node=NodeConfig(mode="node", method="euler",
                                         grad_mode=grad_mode))
    tcfg = TrainConfig(loss_chunk=8)
    state = jax.eval_shape(lambda k: init_train_state(k, arch, tcfg),
                           jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    return jax.jit(make_train_step(arch, tcfg)).lower(
        state, batch).compile().as_text()


def _cnf_step_text() -> str:
    from repro.models.cnf import CNFConfig, cnf_nll, init_cnf
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    cfg = CNFConfig(dim=4, hidden=(8, 8), adaptive=True, rtol=1e-5,
                    atol=1e-7, max_steps=16, combine_backend="pallas")
    acfg = AdamWConfig()

    def step(state, u, eps):
        loss, g = jax.value_and_grad(cnf_nll)(state["params"], u, eps, cfg)
        params, opt = adamw_update(state["params"], g, state["opt"], 1e-3,
                                   acfg)
        return {"params": params, "opt": opt}, loss

    def init(key):
        params = init_cnf(key, cfg)
        return {"params": params, "opt": adamw_init(params, acfg)}

    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    return jax.jit(step).lower(state, x, x).compile().as_text()


def _parse(text: str) -> dict:
    """Instruction name -> (opcode, op_name, computation it calls,
    computation it lies in)."""
    out, comp = {}, None
    names = bscopes.op_names_of_text(text)
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c and not line.startswith(" "):
            comp = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            calls = _CALLS.search(line)
            out[m.group(1)] = (m.group(2), names[m.group(1)],
                               calls.group(1) if calls else None, comp)
    return out


@functools.lru_cache(maxsize=None)
def _step(case: str) -> dict:
    return _parse(_cnf_step_text() if case == "cnf" else _lm_step_text(case))


def _ops(case: str) -> list:
    return [v[1] for v in _step(case).values() if v[1]]


@pytest.mark.parametrize("case", CASES)
def test_matmuls_and_kernels_lie_in_a_phase(case):
    parsed = _step(case)
    by_comp = {}
    for name, (op, _, _, comp) in parsed.items():
        by_comp.setdefault(comp, []).append(op)
    heavy = [n for n, (op, _, calls, _) in parsed.items()
             if op in HEAVY or (op == "fusion" and any(
                 o in HEAVY for o in by_comp.get(calls, ())))]
    assert heavy
    phases = (scopes.ODE_SOLVE, scopes.LM_LOSS, scopes.OPTIMIZER)
    stray = [(n, parsed[n][1]) for n in heavy
             if not any(bscopes.under(parsed[n][1], s) for s in phases)]
    assert not stray, stray


@pytest.mark.parametrize("case", CASES)
def test_every_phase_has_ops(case):
    found = {bscopes.phase(o) for o in _ops(case)}
    want = {"solve_fwd", "solve_bwd", "optim"}
    if case != "cnf":
        want.add("loss")
    assert want <= found


@pytest.mark.parametrize("case", CASES)
def test_gradient_sums_are_named_only_where_the_adjoint_makes_them(case):
    named = [o for o in _ops(case)
             if bscopes.under(o, scopes.ADJOINT_ACCUMULATE)]
    if case == "remat_step":
        assert not named
    else:
        assert named
        # the sums run in the backward of the solve, after its replay
        assert all(bscopes.phase(o) == "solve_bwd" for o in named)


@pytest.mark.parametrize("case", LM_CASES)
def test_attention_backward_is_named(case):
    ops = _ops(case)
    bwd = [o for o in ops if bscopes.backward(o, scopes.ATTENTION)]
    # ops the compiler made carry a stack relative to their region; those
    # with the whole stack all lie in the backward of the solve
    whole = [o for o in bwd if o.startswith("jit(")]
    assert whole and all(bscopes.phase(o) == "solve_bwd" for o in whole)
    if case == "symplectic":
        # the custom VJP's backward, run inside the replay
        assert any("transpose(jvp(attention))" in o for o in ops)


@pytest.mark.parametrize("case", LM_CASES)
def test_replayed_attention_forward_is_not_attention_backward(case):
    # the Pallas forward kernel as the solve's backward recomputes it:
    # the symplectic replay, or remat's recompute of the step
    replayed = [o for o in _ops(case)
                if "flash_attention_pallas" in o
                and bscopes.backward(o, scopes.ODE_SOLVE)]
    assert replayed
    assert not any(bscopes.backward(o, scopes.ATTENTION) for o in replayed)
    forward = [o for o in _ops(case)
               if "flash_attention_pallas" in o
               and bscopes.phase(o) == "solve_fwd"]
    assert forward
    assert not any(bscopes.backward(o, scopes.ATTENTION) for o in forward)
