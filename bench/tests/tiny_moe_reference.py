"""Test fixture: the reference module of a routed-expert layout with two
layers to a scanned group, a sliding-window one and then a full one,
written as a later change adds one for a new configuration.  ``score``
stands in for a plain forward with a fixed log-probability per precision,
so that a test sees that it is the one called, with the weights that its
own ``shapes`` laid out."""

from __future__ import annotations

import numpy as np

SCORE = {"f32": -0.5, "fp8": -1.5}      # each token's logprob, by precision


def shapes(c: dict) -> dict:
    """Leaf path -> (shape, kind, fan-in), in the program's layout: one
    scanned group of ``len(c["pattern"])`` layers, each with QKV biases and
    a routed MLP whose experts the program pads to a multiple of 16."""
    D, V = c["hidden_size"], c["vocab_size"]
    H, K, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, F, Fs = (c["num_experts"], c["moe_intermediate_size"],
                c["shared_expert_intermediate_size"])
    Ep = -(-E // 16) * 16
    P = len(c["pattern"])
    G = c["num_hidden_layers"] // P
    leaves = {"embed": ((V, D), "w", D), "lm_head": ((D, V), "w", D),
              "final_norm/scale": ((D,), "norm", 0)}
    for j in range(P):
        sub = f"groups/sub{j}/"
        leaves.update({sub + k: v for k, v in {
            "ln1/scale": ((G, D), "norm", 0),
            "ln2/scale": ((G, D), "norm", 0),
            "attn/wq": ((G, D, H, dh), "w", D),
            "attn/wk": ((G, D, K, dh), "w", D),
            "attn/wv": ((G, D, K, dh), "w", D),
            "attn/wo": ((G, H, dh, D), "w", H * dh),
            "attn/bq": ((G, H, dh), "bias", 0),
            "attn/bk": ((G, K, dh), "bias", 0),
            "attn/bv": ((G, K, dh), "bias", 0),
            "mlp/router": ((G, D, E), "router", D),
            "mlp/experts/wi": ((G, Ep, D, F), "w", D),
            "mlp/experts/wg": ((G, Ep, D, F), "w", D),
            "mlp/experts/wo": ((G, Ep, F, D), "w", F),
            "mlp/shared/wi": ((G, D, Fs), "w", D),
            "mlp/shared/wg": ((G, D, Fs), "w", D),
            "mlp/shared/wo": ((G, Fs, D), "w", Fs),
            "mlp/shared_gate": ((G, D, 1), "router", D),
        }.items()})
    return leaves


def score(weights: dict, c: dict, tokens, *, precision: str = "f32"):
    if sorted(weights["groups"]) != [f"sub{j}" for j in range(len(c["pattern"]))]:
        raise ValueError("weights not in this module's layout")
    n = len(tokens) - 1
    return np.full(n, SCORE[precision]), np.zeros(n)
