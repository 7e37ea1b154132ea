"""Counts of the two ragged kernels at Qwen3-8B widths (32 query heads, 8
KV heads, head_dim 128, an f32 pool and bf16 activations), worked by hand."""

from __future__ import annotations

import pytest

from bench import flops, peaks

KW = dict(heads=32, kv_heads=8, head_dim=128, pool_itemsize=4, act_itemsize=2)


def test_decode_counts():
    f, b = flops.decode_attention([1000, 0], **KW)
    assert f == 4 * 1000 * 32 * 128 == 16_384_000
    # K and V of 1000 tokens (2 * 8 * 128 * 4 B each) + q and out of one row
    assert b == 8_192_000 + 2 * 32 * 128 * 2 == 8_208_384


def test_prefill_counts():
    f, b = flops.prefill_attention([(512, 256)], **KW)
    pairs = 256 * 512 + 256 * 257 / 2            # prefix + causal chunk
    assert pairs == 163_968
    assert f == 4 * pairs * 32 * 128 == 2_686_451_712
    assert b == 2 * 8 * 128 * 4 * 512 + 256 * 80 * 128 * 2 == 9_437_184


def test_model_flops_and_roofline():
    c = {"num_hidden_layers": 8, "hidden_size": 4096, "intermediate_size": 12288,
         "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
         "vocab_size": 151936}
    per_layer = 4096 * 48 * 128 + 32 * 128 * 4096 + 3 * 4096 * 12288
    assert flops.layer_matmul_params(c) == per_layer == 192_937_984
    got = flops.model_flops(c, tokens=1, attn_pairs=100, logit_rows=1)
    assert got == 2 * 8 * per_layer + 4 * 100 * 8 * 32 * 128 + 2 * 4096 * 151936
    peak = peaks.for_kind("TPU v5 lite")
    # 819 MB read in 2 ms at 819 GB/s is half the roofline
    assert flops.roofline_share(0, 819e6, 2e-3, peak) == pytest.approx(50.0)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("cpu")
