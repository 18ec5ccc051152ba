"""Operation and byte counts, worked by hand at small shapes, and the
peaks table."""
import pytest

import counts
import harness

# 2 layers, d 8, 2 heads of 4, 1 kv head, ff 16, vocab 10
CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "num_hidden_layers": 2, "vocab_size": 10}


def test_matmul_params_by_hand():
    # per layer: q 8x8 + o 8x8 = 128, k 8x4 + v 8x4 = 64, mlp 3*8*16 = 384
    # head: 10x8 = 80
    assert counts.matmul_params(CFG) == 2 * (128 + 64 + 384) + 80


def test_decode_flops_by_hand():
    # two sequences attending 3 and 5 positions
    mm = 2 * 1232 * 2
    attn = 4 * (3 + 5) * 2 * 4 * 2
    assert counts.decode_flops(CFG, [3, 5]) == mm + attn


def test_decode_attention_bytes_by_hand():
    # keys and values: (3 + 5) positions x 1 kv head x 4 lanes x 2;
    # queries and outputs: 2 sequences x 2 heads x 4 lanes x 2; bf16; x2
    # layers
    want = ((3 + 5) * 1 * 4 * 2 + 2 * 2 * 4 * 2) * 2 * 2
    assert counts.decode_attention_bytes(CFG, [3, 5]) == want


def test_inactive_sequences_cost_nothing():
    assert counts.decode_flops(CFG, []) == 0
    assert counts.decode_attention_bytes(CFG, []) == 0
    assert counts.total(counts.decode_flops, CFG, [[3], [5]]) == \
        counts.decode_flops(CFG, [3]) + counts.decode_flops(CFG, [5])


def test_peaks_of_a_v5e():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="not in the peaks table"):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("cpu")
