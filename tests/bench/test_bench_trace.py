"""The reduction from a trace to busy time, idle share, kernel sums and
the breakdown, on traces whose answers are counted by hand."""
import bench_testing  # noqa: F401  (puts the repository root on sys.path)

import json

import pytest

from bench import kernels, trace

DATA = bench_testing.ROOT / "tests" / "bench" / "data"

# two steps on the host clock, 0-100 and 120-200 ns; device 0 runs a
# while op 5-65 whose body holds ops 10-40 and 40-60, an op 130-150 and
# one 190-210 that ends past the window; the batch span covers 100-130
HAND = trace.Trace(
    devices={"/device:TPU:0": [
        ("while.0", 5, 60),
        ("butcher_combine_pallas.1", 10, 30),
        ("fusion.2", 40, 20),
        ("flash_attention_pallas.3", 130, 20),
        ("all-reduce.4", 190, 20)]},
    host=[("bench.step", 0, 100), ("bench.batch", 100, 30),
          ("bench.step", 120, 80), ("bench.fetch", 160, 40)])


def test_busy_union_and_idle_share_by_hand():
    assert trace.window(HAND) == (0, 200)
    # union inside [0, 200): 5-65, 130-150, 190-200 -> 60 + 20 + 10
    assert trace.busy_seconds(HAND) == pytest.approx(90e-9)
    assert trace.window_seconds(HAND) == pytest.approx(200e-9)
    assert trace.idle_gaps(HAND.devices["/device:TPU:0"], 0, 200) == [
        (0, 5), (65, 130), (150, 190)]


def test_kernel_sums_by_name():
    # ops that end past the window are not counted
    assert trace.op_seconds(HAND, kernels.matcher("combine")) == \
        pytest.approx(30e-9)
    assert trace.op_seconds(HAND, kernels.matcher("attn_fwd")) == \
        pytest.approx(20e-9)
    assert trace.op_seconds(HAND, kernels.matcher("rmsnorm")) == 0


def test_breakdown_names_gaps_by_host_span():
    bd = trace.breakdown(HAND)
    # leaf ops only: the while op's time is its body's
    assert [n for n, _ in bd["device_ops"]] == [
        "butcher_combine_pallas.1", "fusion.2", "flash_attention_pallas.3"]
    assert bd["device_ops"][0][1] == pytest.approx(30e-9)
    # longest gap 65-130: its middle (97) lies in the first step only
    assert bd["idle_gaps"][0] == ["between steps", pytest.approx(65e-9)]
    assert bd["idle_gaps"][1] == ["bench.fetch", pytest.approx(40e-9)]


def test_op_name_of_a_tpu_event():
    assert trace.op_name("%flash_attention_pallas.14 = f32[8,16,1024,128]"
                         "{3,2,1,0} custom-call(f32[8] %copy.480)") == \
        "flash_attention_pallas.14"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_trace_round_trips_json():
    again = trace.Trace.from_json(json.loads(json.dumps(HAND.to_json())))
    assert again == HAND


def _brute_busy(events, lo, hi):
    """Busy nanoseconds by marking every nanosecond of the window."""
    import numpy as np
    mark = np.zeros(hi - lo, bool)
    for _, s, d in events:
        mark[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
    return int(mark.sum())


def test_recorded_chip_trace_against_brute_force():
    """A 2 ms slice of a traced CNF step on a TPU v5 lite, as
    ``trace.extract`` read it."""
    tr = trace.Trace.load(DATA / "trace_cnf.json")
    lo, hi = trace.window(tr)
    (dev, events), = tr.devices.items()
    assert dev.startswith("/device:TPU")
    assert trace.busy_ns(events, lo, hi) == _brute_busy(events, lo, hi)
    gaps = trace.idle_gaps(events, lo, hi)
    assert sum(e - s for s, e in gaps) + trace.busy_ns(events, lo, hi) \
        == hi - lo
    comb = [e for e in events if e[0].startswith("butcher_combine")]
    assert comb, "the CNF step runs the combine kernel"
    assert trace.op_seconds(tr, kernels.matcher("combine")) == \
        pytest.approx(sum(d for _, s, d in comb
                          if s >= lo and s + d <= hi) / 1e9)


def test_kernel_time_and_roofline_share_by_hand():
    import types
    ctx = types.SimpleNamespace(trace=HAND, trace_steps=2, peaks={
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    # the attention op runs 20 ns over 2 traced steps
    assert kernels.op_seconds_per_step(ctx, "attn_fwd") == \
        pytest.approx(10e-9)
    assert kernels.op_seconds_per_step(ctx, "rmsnorm") is None
    # 1e4 flops bound it at 10 ns against 1e2 bytes' 1 ns: half of 20 ns
    assert kernels.roofline_pct(ctx, "attn_fwd", lambda n: (1e4, 1e2)) == \
        pytest.approx(50.0)
    # work that cannot be counted leaves the share out; so does no trace
    assert kernels.roofline_pct(ctx, "attn_fwd", lambda n: None) is None
    ctx.trace = None
    assert kernels.roofline_pct(ctx, "attn_fwd", lambda n: (1e4, 1e2)) \
        is None
