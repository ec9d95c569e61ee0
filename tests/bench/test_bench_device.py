"""The benchmark runs on a TPU or not at all; peaks come from the table."""
import bench_testing  # noqa: F401  (puts the repository root on sys.path)

import json

import jax
import numpy as np
import pytest

from bench import device, generate


def test_run_refuses_a_cpu_and_prints_no_result():
    rc, out, err = bench_testing.run_cell(bench_testing.ROOT, "lm-qwen3-sym")
    assert rc == 2
    assert out == ""
    assert "no TPU" in err


def test_require_tpu_counts_chips():
    class Dev:
        def __init__(self, platform):
            self.platform, self.device_kind = platform, "TPU v5 lite"

    with pytest.raises(device.NoAccelerator, match="no TPU"):
        device.require_tpu(jax.devices(), 1)
    with pytest.raises(device.NoAccelerator, match="asks for 4"):
        device.require_tpu([Dev("tpu")], 4)
    assert len(device.require_tpu([Dev("tpu")] * 4, 4)) == 4


def test_peaks_table_is_keyed_and_sourced():
    table = json.loads(open(device.PEAKS_FILE).read())
    for kind, row in table.items():
        assert row["source"]
        assert row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        device.peaks("TPU v9 imaginary")


def test_seed_words_keep_all_64_bits():
    a = generate.seed_words(2 ** 33 + 7)
    b = generate.seed_words(7)
    assert a.dtype == np.uint32 and list(a) == [2, 7]
    assert list(b) == [0, 7]
    with pytest.raises(ValueError):
        generate.seed_words(-1)


def test_same_seed_same_inputs_and_rows_differ():
    a = generate.markov_tokens(2 ** 31 + 5, 3, 4, 33, 151936, 17)
    b = generate.markov_tokens(2 ** 31 + 5, 3, 4, 33, 151936, 17)
    c = generate.markov_tokens(2 ** 31 + 5, 4, 4, 33, 151936, 17)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()
    assert len({tuple(r) for r in a["tokens"]}) == 4
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()
    mix = generate.gaussian_mixture(9, 43, 5)
    x = generate.mixture_batch(9, 0, mix, 4000)["u"]
    assert x.shape == (4000, 43) and x.dtype == np.float32
    assert abs(float(x.mean())) < 0.1 and abs(float(x.std()) - 1) < 0.1


def test_cnf_data_set_is_fixed_and_seeds_draw_the_batches():
    """Every seed trains on the one mixture its traffic names, so the
    adaptive solver's work does not follow the seed; the seed draws the
    batches and the noise."""
    from bench.cell import kind_module, load_cell
    cell = load_cell("cnf-miniboone-sym")
    tr = cell.traffic
    mixes = [generate.gaussian_mixture(tr["mixture_seed"], cell.config["dim"],
                                       tr["mixture"]) for _ in range(2)]
    assert all((mixes[0][k] == mixes[1][k]).all() for k in mixes[0])
    a = generate.mixture_batch(2 ** 32 + 3, 0, mixes[0], 16)
    b = generate.mixture_batch(5, 0, mixes[0], 16)
    assert not (a["u"] == b["u"]).any() and not (a["eps"] == b["eps"]).any()
    src = open(kind_module(cell).__file__).read()
    assert "gaussian_mixture(seed" not in src
