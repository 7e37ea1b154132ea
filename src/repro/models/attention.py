"""GQA attention: RoPE, masks, chunked prefill/train path, decode path.

The jnp path here is the reference/roofline implementation; Pallas TPU kernels
in ``repro.kernels`` are drop-in replacements for the same math (selected via
``ModelRuntime.use_pallas``).

Memory discipline for long sequences:
  * train/prefill processes queries in blocks of ``q_block`` via ``lax.map``;
  * "local" (sliding-window) layers slice a (q_block + window)-wide KV band
    with ``dynamic_slice`` so window attention costs O(S * W), not O(S^2);
  * "global" causal layers compute the full KV per q-block and mask — the
    ~2x causal FLOP waste is visible in the roofline MODEL/HLO ratio and is
    reclaimed by the Pallas kernel on real TPUs (block skipping).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import rms_norm, softcap

NEG_INF = -2.0e38


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_inv_freq(head_dim: int, theta: float) -> jax.Array:
    exponent = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    return jnp.asarray(1.0 / (theta ** exponent), dtype=jnp.float32)


def apply_rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array) -> jax.Array:
    """x: [B, S, H, dh]; positions: [B, S] or [S] (int32). Split-half RoPE."""
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# core attention math (shared by prefill block & decode)
# --------------------------------------------------------------------------- #
def _attend(q, k, v, mask, cap: float):
    """q: [B,Sq,K,G,dh], k/v: [B,T,K,dh], mask: broadcastable to [B,K,G,Sq,T].

    Returns [B,Sq,K,G,dh].  Scores/softmax in f32.
    """
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    if cap:
        scores = softcap(scores, cap)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out


def _split_heads(q, n_kv):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _merge_heads(o):
    b, s, k, g, d = o.shape
    return o.reshape(b, s, k * g, d)


# --------------------------------------------------------------------------- #
# train / prefill
# --------------------------------------------------------------------------- #
def attention_fwd(q, k, v, *, causal: bool, window: int, cap: float,
                  q_block: int = 512) -> jax.Array:
    """Full-sequence attention (train/prefill).

    q: [B,S,H,dh] (already roped/scaled), k/v: [B,S,K,dh] (roped).
    window > 0 => sliding-window (local) causal attention.
    causal=False => bidirectional encoder attention (window ignored).
    """
    B, S, H, dh = q.shape
    K = k.shape[2]
    q = _split_heads(q, K)

    if S <= q_block:
        qpos = jnp.arange(S)
        mask = None
        if causal:
            mask = qpos[:, None] >= qpos[None, :]
            if window and window < S:
                mask &= (qpos[:, None] - qpos[None, :]) < window
            mask = mask[None, None, None]
        return _merge_heads(_attend(q, k, v, mask, cap))

    assert S % q_block == 0, (S, q_block)
    n_blocks = S // q_block
    use_band = causal and bool(window) and window < S

    if use_band:
        # KV band of width q_block + window (rounded up to q_block multiple)
        band = int(np.ceil((q_block + window) / q_block)) * q_block
        band = min(band, S)

    @jax.checkpoint  # flash-style: recompute scores/probs in backward
    def one_block(i):
        qs = i * q_block
        qb = jax.lax.dynamic_slice_in_dim(q, qs, q_block, axis=1)
        qpos = qs + jnp.arange(q_block)
        if use_band:
            ks = jnp.clip(qs + q_block - band, 0, S - band)
            kb = jax.lax.dynamic_slice_in_dim(k, ks, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ks, band, axis=1)
            kpos = ks + jnp.arange(band)
        else:
            kb, vb = k, v
            kpos = jnp.arange(S)
        if not causal:
            return _attend(qb, kb, vb, None, cap)
        mask = qpos[:, None] >= kpos[None, :]
        if window and window < S:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        return _attend(qb, kb, vb, mask[None, None, None], cap)

    blocks = jax.lax.map(one_block, jnp.arange(n_blocks))  # [n,B,qb,K,G,dh]
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, S, K, H // K, dh)
    return _merge_heads(out)


# --------------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------------- #
def attention_decode(q, k_cache, v_cache, kv_positions, q_positions, *,
                     window: int, cap: float) -> jax.Array:
    """One-token decode against a cache slab.

    q: [B,1,H,dh] roped/scaled.  k_cache/v_cache: [B,T,K,dh] (roped at write).
    kv_positions: [B,T] absolute position held in each slot (-1 => empty).
    q_positions: [B] absolute position of the query token.
    """
    K = k_cache.shape[2]
    q = _split_heads(q, K)
    valid = kv_positions >= 0
    mask = valid & (kv_positions <= q_positions[:, None])
    if window:
        mask &= (q_positions[:, None] - kv_positions) < window
    mask = mask[:, None, None, None, :]  # [B,1,1,1,T]
    out = _attend(q, k_cache, v_cache, mask, cap)
    return _merge_heads(out)


# --------------------------------------------------------------------------- #
# paged decode / chunked prefill (block-table indexed KV pools)
#
# ORACLES, not the hot path: the serving engine routes paged attention
# through the ragged Pallas kernels (kernels.paged_attention /
# kernels.paged_prefill — HBM reads scale with true context lengths).  The
# dense gather-based implementations below materialize the whole padded
# [B, nb*ps, K, dh] context and survive only as the parity ground truth
# (ModelRuntime.use_pallas=False; tests/test_ragged_serving.py).
# --------------------------------------------------------------------------- #
def gather_pages(pool, block_tables):
    """pool: [P, K, ps, dh]; block_tables: [B, nb] -> [B, nb*ps, K, dh].

    Gathered slot i holds absolute position i (pages are table-ordered);
    padding table entries point at the garbage page and are masked by the
    caller via position validity.
    """
    g = pool[block_tables]                       # [B, nb, K, ps, dh]
    B, nb, K, ps, dh = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * ps, K, dh)


def attention_paged_decode(q, k_pool, v_pool, block_tables, q_positions, *,
                           cap: float) -> jax.Array:
    """One-token decode against paged KV pools.

    q: [B,1,H,dh] roped/scaled.  k_pool/v_pool: [P, K, ps, dh] (roped at
    write).  block_tables: [B, nb].  q_positions: [B] absolute position of
    the query token (== context length already written, minus one... the
    current token's KV must already be written at q_positions).
    """
    k_ctx = gather_pages(k_pool, block_tables)
    v_ctx = gather_pages(v_pool, block_tables)
    B, T = k_ctx.shape[0], k_ctx.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    return attention_decode(q, k_ctx, v_ctx, kv_pos, q_positions,
                            window=0, cap=cap)


def attention_paged_prefill(q, k, v, k_pool, v_pool, block_tables, offsets,
                            chunk_lens, *, cap: float) -> jax.Array:
    """One prefill chunk against its own K/V plus the paged prefix.

    q/k/v: [B, C, H|K, dh] roped (positions offsets+i) — q already scaled.
    offsets: [B] tokens already in the pool for each row (prefix length).
    chunk_lens: [B] valid tokens in this chunk (rows are right-padded).
    The chunk's K/V is attended directly (it is written to pages after).
    """
    B, C = q.shape[0], q.shape[1]
    K = k.shape[2]
    qs = _split_heads(q, K)
    k_pre = gather_pages(k_pool, block_tables)
    v_pre = gather_pages(v_pool, block_tables)
    T = k_pre.shape[1]
    kk = jnp.concatenate([k_pre.astype(k.dtype), k], axis=1)   # [B, T+C, K, dh]
    vv = jnp.concatenate([v_pre.astype(v.dtype), v], axis=1)
    qpos = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [B, C]
    kvpos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T)),
         qpos], axis=1)                                              # [B, T+C]
    valid = jnp.concatenate(
        [jnp.arange(T, dtype=jnp.int32)[None] < offsets[:, None],
         jnp.arange(C, dtype=jnp.int32)[None] < chunk_lens[:, None]], axis=1)
    mask = valid[:, None, :] & (kvpos[:, None, :] <= qpos[:, :, None])
    out = _attend(qs, kk, vv, mask[:, None, None], cap)   # [B,C,K,G,dh]
    return _merge_heads(out)


def paged_write(pool, vals, pages, offs):
    """Scatter token K/V into pool pages.

    pool: [P, K, ps, dh]; vals: [n, K, dh]; pages/offs: [n].  Duplicate
    garbage-page destinations are fine (content is never read unmasked).
    """
    return pool.at[pages, :, offs].set(vals.astype(pool.dtype))


# --------------------------------------------------------------------------- #
# qk-norm
# --------------------------------------------------------------------------- #
def maybe_qk_norm(q, k, params, enabled: bool):
    if not enabled:
        return q, k
    q = rms_norm(q, params["q_norm"])
    k = rms_norm(k, params["k_norm"])
    return q, k
