"""Pallas TPU flash-attention (prefill/train forward) with GQA, causal,
sliding-window and logit-softcap support.

TPU adaptation of the FlashAttention online-softmax algorithm
[arXiv:2205.14135]: the MXU consumes (block_q x d) x (d x block_k) tiles
streamed HBM->VMEM by the Pallas pipeline; running (m, l, acc) live in VMEM
scratch across the sequential minor grid dimension (kv blocks).  Fully
masked kv blocks (beyond the causal diagonal or outside the sliding window)
skip their MXU work via ``pl.when`` — this is where the ~2x causal FLOP
waste of the jnp reference path is reclaimed on real hardware.

Grid: (batch, q_heads, q_blocks, kv_blocks); GQA maps q-head h to kv-head
h // (H // K) in the K/V index maps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, cap: float,
            block_q: int, block_k: int, n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability (skip fully-masked blocks entirely)
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if cap:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = qpos >= kpos
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "cap", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    cap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """q: [B, H, S, d]; k/v: [B, K, S, d] -> [B, H, S, d].

    Head-major layout (better MXU tiling than seq-major: the [S, d] tile is
    contiguous per head).  S must be a multiple of the block sizes.
    """
    B, H, S, d = q.shape
    K = k.shape[1]
    G = H // K
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq = S // block_q
    nk = S // block_k
    scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, cap=cap,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            # (m, l, acc) accumulators in VMEM
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
