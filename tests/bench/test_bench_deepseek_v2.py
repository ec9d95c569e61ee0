"""The DeepSeek-V2 cell's own pieces of the harness: its scope readers
(``bench/layer_scopes.py``) on a trace and an op-name map written by hand,
and its host batch against the generator it stands for."""
import bench_testing  # noqa: F401  (the repository root on sys.path)

import numpy as np
import pytest

from bench import cell as bcell
from bench import generate, layer_scopes, scopes, trace
from bench.kinds import deepseek_v2

STEP = "jit(train_step)/"
NAMES = {
    "fusion.1": STEP + "prefix_layers/checkpoint/dot_general",
    "fusion.2": STEP + "transpose(jvp(prefix_layers))/dot_general",
    "sort.3": STEP + "jvp(ode_solve)/while/body/moe_route/sort",
    "fusion.4": STEP + "jvp(ode_solve)/while/body/moe_experts/mul",
    "fusion.5": STEP + "transpose(jvp(ode_solve))/while/body/"
                       "transpose(jvp(moe_experts))/mul",
    "fusion.6": STEP + "transpose(jvp(lm_loss))/dot_general",
    "fusion.7": STEP + "optimizer/mul",
    "scatter.8": STEP + "transpose(jvp())/scatter-add",
    "copy.9": "",
    # the TPU compiler's expansion of a ragged dot keeps no name stack
    "ragged-dot-none.11": "ragged-dot-none",
}
# two steps on the host clock, 0-100 and 100-200 ns; fusion.10 is not an
# instruction of the map
HAND = trace.Trace(
    devices={"/device:TPU:0": [
        ("fusion.1", 0, 10),
        ("fusion.2", 10, 20),
        ("sort.3", 30, 5),
        ("fusion.4", 35, 7),
        ("fusion.5", 42, 14),
        ("fusion.6", 110, 30),
        ("fusion.7", 140, 10),
        ("scatter.8", 150, 8),
        ("copy.9", 160, 4),
        ("fusion.10", 170, 6),
        ("ragged-dot-none.11", 180, 9)]},
    host=[("bench.step", 0, 100), ("bench.step", 100, 100)])
READERS = ("prefix_ms_per_step", "moe_route_ms_per_step",
           "moe_experts_ms_per_step", "unscoped_pct.dsv2")


class Ctx:
    def __init__(self, tr, steps=2):
        self.trace, self.trace_steps, self.job = tr, steps, None


@pytest.fixture
def hand(monkeypatch):
    names = dict(NAMES)
    monkeypatch.setattr(scopes, "op_names", lambda ctx: names)
    monkeypatch.setattr(layer_scopes, "_MEMO", [])
    return names


def test_layer_scope_readers_by_hand(hand):
    got = {m: bcell.metric_reader(m)(Ctx(HAND)) for m in READERS}
    # ns a step over 2 steps, in ms
    assert got["prefix_ms_per_step"] == pytest.approx(30 / 2 * 1e-6)
    assert got["moe_route_ms_per_step"] == pytest.approx(5 / 2 * 1e-6)
    # the two ops under the scope and the unnamed ragged dot
    assert got["moe_experts_ms_per_step"] == pytest.approx(30 / 2 * 1e-6)
    # scatter.8, copy.9 and the unmapped fusion.10 lie under no phase; the
    # prefix is a phase of its own
    assert got["unscoped_pct.dsv2"] == pytest.approx(100 * 18 / 123)


def test_unscoped_share_counts_the_prefix_apart_from_bench_scopes(hand):
    """``unscoped_pct`` (``bench/scopes.py``) reads the prefix layers and
    the unnamed ragged dot as unscoped; this cell's reader does not."""
    ctx = Ctx(HAND)
    assert bcell.metric_reader("unscoped_pct")(ctx) == \
        pytest.approx(100 * (18 + 30 + 9) / 123)
    assert bcell.metric_reader("unscoped_pct.dsv2")(ctx) == \
        pytest.approx(100 * 18 / 123)


def test_a_program_without_the_layer_scopes_reads_none(hand):
    del hand["ragged-dot-none.11"]
    for name in list(hand):
        hand[name] = STEP + "transpose(jvp())/while/body/mul"
    for m in READERS:
        assert bcell.metric_reader(m)(Ctx(HAND)) is None, m
    assert layer_scopes.unscoped_pct(Ctx(None)) is None


@pytest.mark.parametrize("seed,step,batch,length,vocab,noise", [
    (3716000901, 5, 4, 4097, 12800, 17),      # the cell's batch
    (2 ** 33 + 5, 0, 2, 17, 128, 17),         # its smoke batch
    (9, 3, 1, 1000, 151936, 17),
    (7, 1, 3, 2, 12800, 3),
    (1, 2, 2, 1, 50, 3),
])
def test_host_batch_is_the_generators(seed, step, batch, length, vocab,
                                      noise):
    want = generate.markov_tokens(seed, step, batch, length, vocab, noise)
    got = deepseek_v2.markov_tokens(seed, step, batch, length, vocab, noise)
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k])
