"""benchmark/costs.py and peaks.json against hand-checked cases."""

import pytest

from benchmark import costs


def test_355m_at_t1024_is_2_28_gflop_a_token():
    assert costs.gpt2_num_params(24, 1024, 50257, 1024) == 354_823_168
    flops = costs.gpt2_train_flops_per_token(24, 1024, 50257, 1024, 1024)
    # 6 * 354.8M + 6 * 24 * 1024 * 1024
    assert flops == 6 * 354_823_168 + 150_994_944
    assert round(flops / 1e9, 2) == 2.28


def test_xl_needs_more_than_one_chip():
    n = costs.gpt2_num_params(48, 1600, 50257, 1024)
    assert n == 1_557_611_200
    # fp32 master + two AdamW moments
    assert n * 12 > 16e9


@pytest.mark.parametrize("causal, share", [(True, 0.5), (False, 1.0)])
def test_flash_counts_by_hand(causal, share):
    c = costs.flash_attention_cost(2, 3, 128, 64, causal=causal)
    one_matmul = 2 * 128 * 128 * 64 * share      # per head
    assert c["fwd_flops"] == 2 * one_matmul * 6
    assert c["bwd_flops"] == 5 * one_matmul * 6
    tensor = 2 * 3 * 128 * 64 * 2                 # bf16
    lse = 2 * 3 * 128 * 4
    assert c["fwd_bytes"] == 4 * tensor + lse
    assert c["bwd_bytes"] == 8 * tensor + lse


def test_flash_at_the_cells_shape_is_compute_bound():
    c = costs.flash_attention_cost(16, 16, 1024, 64)
    peaks = costs.device_peaks("TPU v5 lite")
    for k in ("fwd", "bwd"):
        _, bound = costs.least_seconds(c[k + "_flops"], c[k + "_bytes"],
                                       peaks)
        assert bound == "compute"


def test_decode_counts_by_hand():
    c = costs.decode_attention_cost([100, 300], heads=16, head_dim=64)
    assert c["bytes"] == 2 * 16 * 64 * 2 * 400    # k and v, bf16
    assert c["flops"] == 4 * 16 * 64 * 400        # q.K^T and p.V
    seconds, bound = costs.least_seconds(
        c["flops"], c["bytes"], costs.device_peaks("TPU v5 lite"))
    assert bound == "memory"
    assert seconds == pytest.approx(c["bytes"] / 819e9)


def test_decode_bytes_are_what_the_builder_says_a_token_holds():
    # GPT-2 355M in bf16: a key and a value for each of 16 heads of 64
    plain = costs.decode_attention_cost([100, 300], heads=16, head_dim=64)
    assert costs.decode_attention_cost(
        [100, 300], 16, 64, kv_bytes_per_token=2 * 16 * 64 * 2) == plain
    # four key/value heads under sixteen query heads: a quarter of the
    # bytes, the same operations
    grouped = costs.decode_attention_cost(
        [100, 300], 16, 64, kv_bytes_per_token=2 * 4 * 64 * 2)
    assert grouped["bytes"] * 4 == plain["bytes"]
    assert grouped["flops"] == plain["flops"]


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    row = costs.device_peaks("TPU v5 lite")
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "source" in row
    with pytest.raises(KeyError, match="no peaks row"):
        costs.device_peaks("cpu")
