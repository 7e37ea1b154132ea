"""Operation and byte counts of the kernels and model steps, at the true
lengths the work needs, whatever implements it.

``step_flops`` counts a model step layer by layer, from a plan read off
the configuration's published keys: each layer's attention is full or
sliding, each MLP dense or routed.

The KV byte counts follow ``core/perfmodel.py``'s
``decode_kv_read_bytes`` / ``prefill_kv_read_bytes`` (copied, so that a
later change of the program cannot move the yardstick), with the pool's
itemsize as read at run time instead of a fixed 2 bytes.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

# published keys that count leading dense layers of a routed model
DENSE_PREFIX_KEYS = ("first_k_dense_replace", "first_k_dense", "num_dense_layers")


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


def _window(c: dict) -> int:
    w = c.get("sliding_window")
    return int(w) if w and c.get("use_sliding_window") is not False else 0


def layer_plan(c: dict) -> List[Tuple[int, str]]:
    """Per layer, the keys a query may attend to (0: all, a full-attention
    layer; W: the last W, a sliding one) and its MLP, "dense" or "routed".

    Sliding layers are those ``layer_types`` calls ``sliding_attention``;
    without ``layer_types``, every layer when ``sliding_window`` is in
    effect.  Routed MLPs are those ``mlp_layer_types`` calls ``sparse``;
    without it, every layer past the leading dense ones when
    ``num_experts`` is stated."""
    L, W = c["num_hidden_layers"], _window(c)
    types = c.get("layer_types") or ["sliding_attention"] * L
    attn = [W if t == "sliding_attention" else 0 for t in types[:L]]
    if c.get("mlp_layer_types"):
        mlp = ["routed" if t == "sparse" else "dense"
               for t in c["mlp_layer_types"][:L]]
    elif c.get("num_experts"):
        k = next((int(c[key]) for key in DENSE_PREFIX_KEYS if c.get(key)), 0)
        mlp = ["dense"] * min(k, L) + ["routed"] * max(L - k, 0)
    else:
        mlp = ["dense"] * L
    return list(zip(attn, mlp))


def mlp_matmul_params(c: dict, kind: str) -> float:
    """Weights one MLP multiplies each token by: a dense SwiGLU of
    ``intermediate_size``, or a routed one: ``num_experts_per_tok`` experts
    of ``moe_intermediate_size``, the router, and shared experts where the
    configuration states them."""
    D = c["hidden_size"]
    if kind == "dense":
        return 3 * D * c["intermediate_size"]
    Fe = c["moe_intermediate_size"]
    shared = (c.get("shared_expert_intermediate_size")
              or c.get("n_shared_experts", 0) * Fe)
    return 3 * D * (c["num_experts_per_tok"] * Fe + shared) + D * c["num_experts"]


def attended(off: int, take: int, window: int) -> float:
    """(query, key) pairs of ``take`` queries at positions off .. off+take-1,
    each attending causally to the ``window`` keys before it and itself
    (0: to all of them)."""
    lo, hi = off + 1, off + take                 # keys of the first, last query
    if not window or hi <= window:
        return take * off + take * (take + 1) / 2.0
    if lo > window:
        return float(take * window)
    return (lo + window) * (window - lo + 1) / 2.0 + (hi - window) * window


def step_flops(c: dict, steps: Iterable[dict]) -> float:
    """Forward FLOPs of model steps at true lengths, layer by layer.  Each
    step is a traced step's shapes: ``decode_ctx``, the context (keys
    attended, itself included) of each decoded token, and ``prefill_rows``,
    ``(offset, take, last)`` for each prefill row: ``take`` tokens after
    ``offset`` pooled ones, with a row of logits where ``last`` ends the
    context.  A sliding layer of window W counts ``min(ctx, W)`` keys per
    decoded token and ``min(q + 1, W)`` per prefill query at position q."""
    D, V, H = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    dh = c.get("head_dim") or D // H
    K = c["num_key_value_heads"]
    plan = layer_plan(c)
    pairs = {w: 0.0 for w, _ in plan}
    tokens = logits = 0
    for s in steps:
        for ctx in s["decode_ctx"]:
            tokens += 1
            logits += 1
            for w in pairs:
                pairs[w] += min(ctx, w) if w else ctx
        for off, take, last in s["prefill_rows"]:
            tokens += take
            logits += int(last)
            for w in pairs:
                pairs[w] += attended(off, take, w)
    attn_params = D * (H + 2 * K) * dh + H * dh * D
    params = sum(attn_params + mlp_matmul_params(c, m) for _, m in plan)
    return (2.0 * tokens * params + 4.0 * H * dh * sum(pairs[w] for w, _ in plan)
            + 2.0 * logits * D * V)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take over the time taken, in %."""
    floor = max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
