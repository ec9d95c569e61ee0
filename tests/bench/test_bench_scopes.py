"""Per-phase device time from the program's named scopes
(``bench/scopes.py``), on a trace and an op-name map written by hand, and
once end to end on a smoke cell."""
import bench_testing as bt

import json

import pytest

from bench import cell as bcell
from bench import scopes, trace

SOLVE_FWD = "jit(train_step)/jvp(ode_solve)/while/body/closed_call"
SOLVE_BWD = "jit(train_step)/transpose(jvp(ode_solve))/while/body/closed_call"
NAMES = {
    "while.0": "jit(train_step)/jvp(ode_solve)/while",
    "flash_attention_pallas.1": SOLVE_FWD + "/attention/jit("
                                "flash_attention_pallas)/while/body",
    "select_add_fusion.2": SOLVE_BWD + "/adjoint_accumulate/add",
    "fusion.3": SOLVE_BWD + "/transpose(jvp(attention))/transpose(jvp())/"
                            "dot_general",
    "fusion.4": "jit(train_step)/transpose(jvp(lm_loss))/while/body/"
                "closed_call/checkpoint/dot_general",
    "fusion.5": "jit(train_step)/optimizer/mul",
    "copy.6": "",
}
# two steps on the host clock, 0-100 and 100-200 ns; a while op 0-60
# wraps three ops of its body; fusion.7 is not an instruction of the map
HAND = trace.Trace(
    devices={"/device:TPU:0": [
        ("while.0", 0, 60),
        ("flash_attention_pallas.1", 5, 20),
        ("select_add_fusion.2", 25, 15),
        ("fusion.3", 40, 20),
        ("fusion.4", 110, 30),
        ("fusion.5", 140, 10),
        ("copy.6", 150, 4),
        ("fusion.7", 160, 6)]},
    host=[("bench.step", 0, 100), ("bench.step", 100, 100)])


class Ctx:
    def __init__(self, tr, steps=2):
        self.trace, self.trace_steps, self.job = tr, steps, None


@pytest.fixture
def hand(monkeypatch):
    names = dict(NAMES)
    monkeypatch.setattr(scopes, "op_names", lambda ctx: names)
    monkeypatch.setattr(scopes, "_MEMO", [])
    return names


def test_leaf_ops_only_and_parts_by_hand():
    p = scopes.split(HAND, NAMES, steps=2)
    # the while op's 60 ns are its body's ops, counted once: 20+15+20
    # in the solve, 30 loss, 10 optimizer, 4 + 6 unscoped; per step
    assert p["total"] == pytest.approx(105e-9 / 2)
    assert p["solve_fwd"] == pytest.approx(20e-9 / 2)
    assert p["solve_bwd"] == pytest.approx(35e-9 / 2)
    assert p["grad_accum"] == pytest.approx(15e-9 / 2)
    assert p["attn_bwd"] == pytest.approx(20e-9 / 2)
    assert p["loss"] == pytest.approx(30e-9 / 2)
    assert p["optim"] == pytest.approx(10e-9 / 2)
    assert p["unscoped"] == pytest.approx(10e-9 / 2)
    assert p["mapped"] == pytest.approx(99e-9 / 2)


def test_disjoint_parts_sum_to_the_leaf_total():
    for tr in (HAND, trace.Trace.load(str(bt.ROOT / "tests" / "bench" /
                                          "data" / "trace_cnf.json"))):
        # names of the recorded trace are not in the map: all unscoped
        p = scopes.split(tr, NAMES, steps=3)
        parts = sum(p[k] for k in ("solve_fwd", "solve_bwd", "loss",
                                   "optim", "unscoped"))
        assert parts == pytest.approx(p["total"], rel=1e-12)
        assert p["total"] > 0


# (op_name, scope, under it, in its backward), from compiled steps
STACKS = [
    # forward solve: the kernel as the solve runs it
    (SOLVE_FWD + "/attention/jit(flash_attention_pallas)/while/body/dot",
     "attention", True, False),
    (SOLVE_FWD + "/attention/jit(flash_attention_pallas)/while/body/dot",
     "ode_solve", True, False),
    # the symplectic replay recomputes the attention forward for its VJP
    (SOLVE_BWD + "/jvp(attention)/jit(flash_attention_pallas)/while/dot",
     "attention", True, False),
    (SOLVE_BWD + "/jvp(attention)/jit(flash_attention_pallas)/while/dot",
     "ode_solve", True, True),
    # the custom VJP's backward inside the replay
    (SOLVE_BWD + "/transpose(jvp(attention))/jvp()/reduce_max",
     "attention", True, True),
    # remat: the step's forward recomputed, then its transposed body,
    # where the scopes inside a rematerialized block stay bare
    (SOLVE_BWD + "/checkpoint/rematted_computation/attention/jit("
                 "flash_attention_pallas)/while/body/dot",
     "attention", True, False),
    (SOLVE_BWD + "/checkpoint/attention/transpose(jvp())/mul",
     "attention", True, True),
    # a solve inside a transposed scan (the CNF's components): the
    # transform wraps the loop, the scope stays bare
    ("jit(step)/transpose(jvp())/while/body/closed_call/ode_solve/while/"
     "body/closed_call/cond/branch_1_fun/adjoint_accumulate/add",
     "ode_solve", True, True),
    ("jit(step)/transpose(jvp())/while/body/closed_call/ode_solve/while/"
     "body/closed_call/cond/branch_1_fun/adjoint_accumulate/add",
     "adjoint_accumulate", True, True),
    # the field's own VJP inside the forward solve is forward of the solve
    ("jit(step)/jvp()/while/body/closed_call/ode_solve/while/body/"
     "transpose(jvp())/dot_general", "ode_solve", True, False),
    # the loss recomputes its logits inside its own backward
    ("jit(train_step)/transpose(jvp(lm_loss))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "lm_loss", True, True),
    # a jitted function's name is not a scope
    ("jit(train_step)/transpose(jvp(jit(attention)))/mul", "attention",
     False, False),
    ("jit(train_step)/jvp(jit(flash_attention_pallas))/dot", "attention",
     False, False),
    # a stack the compiler left relative to its region
    ("reduce_sum", "ode_solve", False, False),
]


@pytest.mark.parametrize("op_name,scope,is_under,is_backward", STACKS)
def test_direction_on_nested_stacks(op_name, scope, is_under, is_backward):
    assert scopes.under(op_name, scope) is is_under
    assert scopes.backward(op_name, scope) is is_backward


def test_phase_of_a_stack():
    assert scopes.phase(STACKS[3][0]) == "solve_bwd"
    assert scopes.phase(STACKS[0][0]) == "solve_fwd"
    assert scopes.phase(STACKS[10][0]) == "loss"
    assert scopes.phase("jit(train_step)/optimizer/add") == "optim"
    assert scopes.phase("") == scopes.phase(None) == "unscoped"


def test_op_names_of_compiled_text():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={(f32[2])->f32[2]}",
        "%fused_computation (param_0: f32[2]) -> f32[2] {",
        '  ROOT %add.1 = f32[2]{0} add(%param_0, %param_0), metadata='
        '{op_name="jit(step)/optimizer/add" stack_frame_id=3}',
        "}",
        "ENTRY %main.4 (p.1: f32[2]) -> (f32[2], f32[2]) {",
        '  %fusion.2 = f32[2]{0} fusion(%p.1), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(step)/'
        'transpose(jvp(ode_solve))/add"}',
        "  %copy.3 = f32[2]{0} copy(%fusion.2)",
        "  ROOT %tuple.4 = (f32[2]{0}, f32[2]{0}) tuple(%fusion.2, "
        "%copy.3)",
        "}"])
    assert scopes.op_names_of_text(text) == {
        "add.1": "jit(step)/optimizer/add",
        "fusion.2": "jit(step)/transpose(jvp(ode_solve))/add",
        "copy.3": "", "tuple.4": ""}


NEW_LM = ("solve_fwd_ms_per_step", "solve_bwd_ms_per_step",
          "grad_accum_ms_per_step", "attn_bwd_ms_per_step",
          "loss_ms_per_step", "optim_ms_per_step", "unscoped_pct")
NEW_CNF = ("solve_fwd_ms_per_step.cnf", "solve_bwd_ms_per_step.cnf",
           "unscoped_pct.cnf")


def test_readers_by_hand(hand):
    ctx = Ctx(HAND)
    got = {m: bcell.metric_reader(m)(ctx) for m in NEW_LM + NEW_CNF}
    assert got["solve_fwd_ms_per_step"] == pytest.approx(1e-5)
    assert got["solve_bwd_ms_per_step"] == pytest.approx(1.75e-5)
    assert got["grad_accum_ms_per_step"] == pytest.approx(7.5e-6)
    assert got["attn_bwd_ms_per_step"] == pytest.approx(1e-5)
    assert got["loss_ms_per_step"] == pytest.approx(1.5e-5)
    assert got["optim_ms_per_step"] == pytest.approx(5e-6)
    assert got["unscoped_pct"] == pytest.approx(100 * 10 / 105)
    assert got["solve_bwd_ms_per_step.cnf"] == \
        got["solve_bwd_ms_per_step"]


def test_a_scope_the_program_lacks_reads_none(hand):
    for name in ("select_add_fusion.2", "fusion.3"):
        hand[name] = SOLVE_BWD + "/mul"
    ctx = Ctx(HAND)
    assert bcell.metric_reader("grad_accum_ms_per_step")(ctx) is None
    assert bcell.metric_reader("attn_bwd_ms_per_step")(ctx) is None
    assert bcell.metric_reader("solve_bwd_ms_per_step")(ctx) == \
        pytest.approx(1.75e-5)


def test_a_program_without_scopes_reads_none(hand):
    # the stacks a program without named scopes compiles to
    for name in list(hand):
        hand[name] = "jit(train_step)/transpose(jvp())/while/body/mul"
    ctx = Ctx(HAND)
    for m in NEW_LM + NEW_CNF:
        assert bcell.metric_reader(m)(ctx) is None, m
    assert scopes.ms_per_step(Ctx(None), "loss") is None


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return bt.smoke_tree(tmp_path_factory.mktemp("bench_scopes"))


def test_traced_smoke_run_reads_every_phase(smoke, monkeypatch):
    bt.patch_chip(monkeypatch)
    rc, out, err = bt.run_cell(smoke, "lm-qwen3-sym", seed=2 ** 31 + 29,
                               trace=1)
    assert rc == 0, err
    got = {k: v["value"] for k, v in json.loads(
        out.strip().splitlines()[-1])["metrics"].items()}
    for m in NEW_LM:
        assert got[m] > 0, m
    assert got["unscoped_pct"] < 100
    assert got["grad_accum_ms_per_step"] < got["solve_bwd_ms_per_step"]
    assert got["attn_bwd_ms_per_step"] < got["solve_bwd_ms_per_step"]
    assert any(line.startswith("scopes: leaf ops")
               for line in err.splitlines())
