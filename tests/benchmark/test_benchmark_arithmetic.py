"""The yardstick's arithmetic: required FLOPs against hand-worked numbers,
percentiles with failures as +inf, the spread rule, the peaks table."""

import math

import pytest

import benchtiny  # noqa: F401  (puts the repo root on sys.path)
from benchmark import flops, peaks, stats

GPT2_XL_16L = dict(n_embd=1600, n_head=25, n_layer=16, vocab_size=50257)
PYTHIA_1P4B = dict(hidden_size=2048, num_hidden_layers=24,
                   intermediate_size=8192, vocab_size=50304)


def test_matmul_params_gpt2_xl_16_layers():
    # per layer 12 d^2 = 12 x 2,560,000 = 30,720,000; x 16 = 491,520,000;
    # head 1600 x 50257 = 80,411,200.
    assert flops.matmul_params(GPT2_XL_16L) == 491_520_000 + 80_411_200


def test_matmul_params_pythia_1p4b():
    # per layer 4 d^2 + 2 d d_ff = 16,777,216 + 33,554,432 = 50,331,648;
    # x 24 = 1,207,959,552; head 2048 x 50304 = 103,022,592.
    assert flops.matmul_params(PYTHIA_1P4B) == 1_207_959_552 + 103_022_592


def test_attention_counts_the_causal_half():
    assert flops.causal_pairs(1024) == 524_800          # 1024 x 1025 / 2
    # 16 layers x 4 x 1600 x 524,800 pairs
    assert flops.attention_forward_flops(GPT2_XL_16L, 1024) == \
        16 * 4 * 1600 * 524_800
    full = 16 * 4 * 1600 * 1024 * 1024
    assert flops.attention_forward_flops(GPT2_XL_16L, 1024) < 0.51 * full


def test_train_flops_per_step_gpt2_xl():
    # 6 x 571,931,200 x 8192 tokens = 28,111,562,342,400 (28.1 TFLOP)
    # + 3 x 53,739,520,000 x 8 rows = 1,289,748,480,000 (1.29 TFLOP)
    got = flops.train_flops_per_step(GPT2_XL_16L, batch=8, seq=1024)
    assert got == 28_111_562_342_400 + 1_289_748_480_000
    assert flops.train_attention_flops_per_step(
        GPT2_XL_16L, 8, 1024) == 1_289_748_480_000


def test_train_flops_per_token_pythia():
    per_step = flops.train_flops_per_step(PYTHIA_1P4B, batch=8, seq=2048)
    per_token = per_step / (8 * 2048)
    # 6 x 1,310,982,144 = 7.866 GFLOP + attention 24 x 12 x 2048 x 1024.5
    assert per_token == pytest.approx(7.866e9 + 24 * 12 * 2048 * 1024.5,
                                      rel=1e-4)


def test_attention_bytes_per_step():
    # 12 tensors of 8 x 1024 x 1600 bf16 a layer
    assert flops.train_attention_bytes_per_step(GPT2_XL_16L, 8, 1024) == \
        16 * 12 * 8 * 1024 * 1600 * 2


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_percentile_nearest_rank(q, want):
    assert stats.percentile(list(range(1, 11)), q) == want


def test_failures_count_as_infinity_in_the_tail():
    values = [10.0] * 89 + [math.inf] * 11
    assert stats.percentile(values, 90) == math.inf
    values = [10.0] * 90 + [math.inf] * 10
    assert stats.percentile(values, 90) == 10.0
    assert stats.percentile(values, 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4): q1 = 100.75, q3 = 104.25
    assert stats.iqr_spread(values) == pytest.approx(3.5 / 102.5)


def test_peaks_are_keyed_by_the_exact_device_kind(monkeypatch):
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    monkeypatch.setenv("SMP_PEAK_TFLOPS", "1")       # no override
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for unknown in ("TPU v5", "TPU v5e", "tpu v5 lite", "cpu", ""):
        with pytest.raises(peaks.UnknownDevice):
            peaks.peaks_for(unknown)
