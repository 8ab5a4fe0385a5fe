"""Operation and byte counts against hand-worked shapes; the peaks
table; the statistics."""

import pytest

from benchmarks import flops, peaks, stats

MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_hidden_layers": 4, "vocab_size": 32000}


def test_flash_forward_hand_worked():
    # one sequence of 4096, 32 heads of 128, causal: QK^T and PV are
    # 2 * 2 * 32 * 4096 * 4096 * 128 operations, halved by the mask
    got = flops.flash_attention_counts(1, 4096, 4096, 32, 8, 128, 2)
    assert got["flops"] == 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    q = 4096 * 32 * 128 * 2
    kv = 4096 * 8 * 128 * 2
    assert got["bytes"] == 2 * q + 2 * kv


def test_flash_backward_is_five_products_to_two():
    fwd = flops.flash_attention_counts(2, 1024, 1024, 8, 8, 64, 2)
    bwd = flops.flash_attention_counts(2, 1024, 1024, 8, 8, 64, 2,
                                       backward=True)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"])
    assert bwd["bytes"] == 2 * fwd["bytes"]


def test_flash_non_causal_counts_the_whole_square():
    full = flops.flash_attention_counts(1, 512, 512, 4, 4, 64, 2,
                                        causal=False)
    half = flops.flash_attention_counts(1, 512, 512, 4, 4, 64, 2)
    assert full["flops"] == 2 * half["flops"]


def test_paged_decode_lane_hand_worked():
    # one decode lane over 1000 cached tokens, GQA 32/8, head 128, bf16:
    # 4 * 32 * 128 * 1000 operations; K and V of 8 heads read once
    got = flops.paged_attention_counts([1000], [1], 32, 8, 128, 2)
    assert got["flops"] == 4 * 32 * 128 * 1000
    assert got["bytes"] == 2 * 1000 * 8 * 128 * 2 + 2 * 1 * 32 * 128 * 2


def test_paged_prefill_slice_takes_the_triangle_off():
    # 4 new rows at the end of an 8-token context see 5, 6, 7, 8 keys
    got = flops.paged_attention_counts([8], [4], 2, 1, 16, 2)
    assert got["flops"] == 4 * 2 * 16 * (5 + 6 + 7 + 8)
    two = flops.paged_attention_counts([8, 8], [4, 4], 2, 1, 16, 2)
    assert two["flops"] == 2 * got["flops"]
    assert two["bytes"] == 2 * got["bytes"]


def test_decode_is_memory_bound_and_prefill_compute_bound_on_v5e():
    v5e = peaks.peaks_for("TPU v5 lite")
    decode = flops.paged_attention_counts([1000] * 32, [1] * 32, 32, 8,
                                          128, 2)
    assert flops.roofline_seconds(decode, v5e)[1] == "memory"
    train = flops.flash_attention_counts(1, 4096, 4096, 32, 8, 128, 2)
    seconds, bound = flops.roofline_seconds(train, v5e)
    assert bound == "compute"
    assert seconds == pytest.approx(train["flops"] / 197e12)


def test_train_flops_per_token_hand_worked():
    # per layer: q and o 4096^2 each, k and v 4096*1024 each, three FFN
    # matrices of 4096*14336; head 4096*32000; attention 2*2*4096*2048
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    weights = 4 * layer + 4096 * 32000
    attn = 4 * 2 * 2 * 4096 * 2048
    assert flops.train_flops_per_token(MISTRAL, 4096) == \
        3 * (2 * weights + attn)
    assert 5.5e9 < flops.train_flops_per_token(MISTRAL, 4096) < 7e9


def test_peaks_are_the_published_v5e_figures_and_unknown_is_an_error():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_tflops"], v5e["hbm_gbps"], v5e["hbm_gb"]) == \
        (197.0, 819.0, 16.0)
    assert peaks.peaks_for("TPU v5e") == v5e
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("cpu")
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("TPU v9")


@pytest.mark.parametrize("how,want", [
    ("p50", 3.0), ("p90", 4.6), ("p95", 4.8), ("mean", 3.0), ("sum", 15.0),
    ("max", 5.0), ("count", 5.0)])
def test_series_reducers(how, want):
    assert stats.reduce_series([5, 1, 4, 2, 3], how) == pytest.approx(want)


def test_series_reducers_on_nothing_and_unknown():
    assert stats.percentile([], 90) is None and stats.mean([]) is None
    assert stats.percentile([7], 90) == 7.0
    with pytest.raises(ValueError):
        stats.reduce_series([1], "p99.9")
