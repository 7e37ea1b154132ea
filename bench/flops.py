"""Operation and byte counts of the kernels and model steps, at the true
lengths the work needs, whatever implements it.

The KV byte counts follow ``core/perfmodel.py``'s
``decode_kv_read_bytes`` / ``prefill_kv_read_bytes`` (copied, so that a
later change of the program cannot move the yardstick), with the pool's
itemsize as read at run time instead of a fixed 2 bytes.
"""

from __future__ import annotations

from typing import Iterable


def kv_bytes_per_token(layers: int, kv_heads: int, head_dim: int,
                       itemsize: int) -> float:
    """K and V of one token over ``layers`` layers."""
    return 2.0 * layers * kv_heads * head_dim * itemsize


def decode_attention(ctx_lens: Iterable[int], *, heads: int, kv_heads: int,
                     head_dim: int, pool_itemsize: int, act_itemsize: int):
    """One layer of paged decode attention: one query token per row against
    its ``ctx`` cached tokens.  Returns (flops, bytes)."""
    ctx = [int(c) for c in ctx_lens if c > 0]
    flops = sum(4.0 * c * heads * head_dim for c in ctx)        # QK^T and PV
    kv = kv_bytes_per_token(1, kv_heads, head_dim, pool_itemsize) * sum(ctx)
    qo = 2.0 * len(ctx) * heads * head_dim * act_itemsize
    return flops, kv + qo


def prefill_attention(rows: Iterable[tuple], *, heads: int, kv_heads: int,
                      head_dim: int, pool_itemsize: int, act_itemsize: int):
    """One layer of ragged paged prefill attention.  ``rows`` are
    (offset, chunk_len): the chunk attends to ``offset`` pooled tokens and
    causally to itself.  Returns (flops, bytes)."""
    flops = bytes_ = 0.0
    for off, c in rows:
        if c <= 0:
            continue
        pairs = c * off + c * (c + 1) / 2.0          # (query, key) pairs
        flops += 4.0 * pairs * heads * head_dim
        bytes_ += kv_bytes_per_token(1, kv_heads, head_dim, pool_itemsize) * off
        bytes_ += c * (2 * heads + 2 * kv_heads) * head_dim * act_itemsize
    return flops, bytes_


def layer_matmul_params(c: dict) -> float:
    """Weights one decoder block multiplies each token by."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or D // H
    return D * (H + 2 * K) * dh + H * dh * D + 3 * D * F


def model_flops(c: dict, *, tokens: int, attn_pairs: float,
                logit_rows: int) -> float:
    """Forward FLOPs of ``tokens`` tokens through the configuration's
    blocks, with ``attn_pairs`` (query, key) pairs of attention per layer
    and ``logit_rows`` rows of the output head."""
    L, D, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    H = c["num_attention_heads"]
    dh = c.get("head_dim") or D // H
    return (2.0 * tokens * L * layer_matmul_params(c)
            + 4.0 * attn_pairs * L * H * dh
            + 2.0 * logit_rows * D * V)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take over the time taken, in %."""
    floor = max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
