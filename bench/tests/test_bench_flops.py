"""Counts of the two ragged kernels at Qwen3-8B widths (32 query heads, 8
KV heads, head_dim 128, an f32 pool and bf16 activations), and of model
steps layer by layer at Mellum2-12B-A2.5B widths (sliding-window and full
attention, routed experts), worked by hand."""

from __future__ import annotations

import pytest

from bench import flops, peaks

KW = dict(heads=32, kv_heads=8, head_dim=128, pool_itemsize=4, act_itemsize=2)
QWEN3 = {"num_hidden_layers": 8, "hidden_size": 4096, "intermediate_size": 12288,
         "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
         "vocab_size": 151936}
# huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json, one layer
MELLUM2 = {"num_hidden_layers": 1, "hidden_size": 2304, "intermediate_size": 7168,
           "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
           "vocab_size": 98304, "sliding_window": 1024, "use_sliding_window": True,
           "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896}
MELLUM2_ATTN = 2304 * (32 + 2 * 4) * 128 + 32 * 128 * 2304     # q, k, v and o
LOGITS = 2 * 2304 * 98304                                       # one row of the head


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
    c = QWEN3
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


def test_step_flops_sliding_layer():
    c = {**MELLUM2, "layer_types": ["sliding_attention"], "mlp_layer_types": ["dense"]}
    assert flops.layer_plan(c) == [(1024, "dense")]
    # queries at 900..1155 attend to 901..1024 keys, then to 1024 each
    assert flops.attended(900, 256, 1024) == (901 + 1024) * 124 / 2 + 132 * 1024 \
        == 254_518
    assert flops.attended(900, 256, 0) == 256 * 900 + 256 * 257 / 2 == 263_296
    steps = [{"decode_ctx": [3000], "prefill_rows": [(900, 256, True)]}]
    layer = MELLUM2_ATTN + 3 * 2304 * 7168                 # 70,778,880
    assert flops.step_flops(c, steps) == (2 * 257 * layer
                                          + 4 * 32 * 128 * (1024 + 254_518)
                                          + 2 * LOGITS) == 41_473_114_112


def test_step_flops_routed_layer():
    c = {**MELLUM2, "layer_types": ["full_attention"], "mlp_layer_types": ["sparse"]}
    assert flops.layer_plan(c) == [(0, "routed")]
    # eight experts of 896, and the router's 64 columns
    assert flops.mlp_matmul_params(c, "routed") == 8 * 3 * 2304 * 896 + 2304 * 64
    steps = [{"decode_ctx": [3000], "prefill_rows": []}]
    assert flops.step_flops(c, steps) == (2 * (MELLUM2_ATTN + 49_545_216 + 147_456)
                                          + 4 * 32 * 128 * 3000 + LOGITS) \
        == 643_989_504


@pytest.mark.parametrize("keys,plan", [
    ({}, [(0, "dense")] * 3),
    ({"sliding_window": 1024}, [(1024, "dense")] * 3),
    ({"sliding_window": 1024, "use_sliding_window": False}, [(0, "dense")] * 3),
    ({"num_experts": 64, "first_k_dense_replace": 1},
     [(0, "dense"), (0, "routed"), (0, "routed")]),
    ({"sliding_window": 1024,
      "layer_types": ["sliding_attention", "full_attention"] * 2,
      "mlp_layer_types": ["sparse"] * 4},
     [(1024, "routed"), (0, "routed"), (1024, "routed")]),
], ids=["dense", "all-sliding", "window-off", "dense-prefix", "mellum2-cut"])
def test_layer_plan(keys, plan):
    assert flops.layer_plan({"num_hidden_layers": 3, **keys}) == plan


def test_step_flops_is_model_flops_for_a_dense_model():
    steps = [{"decode_ctx": [1000 + 7 * i, 5000, 1], "prefill_rows": []}
             for i in range(5)]
    steps += [{"decode_ctx": [4000], "prefill_rows": [(512, 256, False),
                                                      (0, 61, True)]}]
    tokens = 5 * 3 + 1 + 256 + 61
    pairs = (sum(1000 + 7 * i + 5001 for i in range(5)) + 4000
             + 256 * 512 + 256 * 257 / 2 + 61 * 62 / 2)
    want = flops.model_flops(QWEN3, tokens=tokens, attn_pairs=pairs,
                             logit_rows=5 * 3 + 1 + 1)
    assert flops.step_flops(QWEN3, steps) == pytest.approx(want, rel=1e-12)
