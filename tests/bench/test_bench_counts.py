"""The FLOP and byte counters against counts made by hand."""
import bench_testing  # noqa: F401  (puts the repository root on sys.path)

import json

from bench import counts


def test_causal_pairs_by_hand():
    assert counts.causal_pairs(4, 4) == 1 + 2 + 3 + 4
    assert counts.causal_pairs(3, 5, q_offset=2) == 3 + 4 + 5
    assert counts.causal_pairs(1024, 1024) == 1024 * 1025 // 2


def test_attention_forward_counts():
    # B=1, H=2 heads sharing Hkv=1, S=4, D=8: 10 causal pairs per head,
    # q k^T and p v at 2*8 flops a pair each
    flops, nbytes = counts.attention_fwd_work(1, 2, 1, 4, 4, 8)
    assert flops == 2 * 10 * 4 * 8
    # read q (2*4*8), k and v (1*4*8 each), write o (2*4*8), float32
    assert nbytes == (64 + 32 + 32 + 64) * 4
    full, _ = counts.attention_fwd_work(1, 2, 1, 4, 4, 8, causal=False)
    assert full == 2 * 16 * 4 * 8


def test_qwen3_flops_per_token():
    cfg = json.loads((bench_testing.ROOT / "bench" / "configs"
                      / "qwen3-0.6b-node.json").read_text())
    per_layer = 2 * 1024 * 2048 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    n = 28 * per_layer + 151936 * 1024
    assert counts.lm_matmul_params(cfg) == n == 595_984_384
    assert counts.lm_flops_per_token(cfg, 1024) == 6 * n + 12 * 28 * 16 \
        * 128 * 1024
    assert abs(counts.lm_flops_per_token(cfg, 1024) / 4.28e9 - 1) < 0.005


def test_cnf_field_flops():
    # 43-860-860-43: 36980 + 739600 + 36980 multiply-adds a sample, twice
    # (field and its vjp), 2 flops each
    assert counts.cnf_field_flops(43, [860, 860], 1000) == \
        4 * 1000 * 813560


def test_step_mfu_reader_by_hand():
    import types
    from bench.cell import metric_reader
    # 10 steps in 2 s of 1e11 flops each on 2 chips of 1e12 flop/s: 25 %
    ctx = types.SimpleNamespace(
        job=types.SimpleNamespace(flops_per_step=lambda: 1e11),
        window_s=2.0, steps=10, devices=[0, 1],
        peaks={"bf16_flops_per_s": 1e12})
    for name in ("step_mfu", "step_mfu.cnf"):
        assert metric_reader(name)(ctx) == 25.0
    ctx.job = object()
    assert metric_reader("step_mfu")(ctx) is None


def test_roofline_names_its_bound():
    t, bound = counts.roofline(2e12, 1e9, 1e12, 1e11)
    assert (t, bound) == (2.0, "compute")
    t, bound = counts.roofline(1e9, 1e11, 1e12, 1e11)
    assert (t, bound) == (1.0, "memory")
