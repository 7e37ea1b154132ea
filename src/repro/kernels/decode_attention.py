"""Pallas TPU decode attention: one query token per sequence against a KV
cache slab, seq-blocked with online softmax (FlashDecoding-style split-K
over the context [arXiv:2311.01282], adapted to TPU: the KV slab streams
HBM->VMEM along the sequential minor grid dim, accumulators in VMEM
scratch).

GQA packs the G = H/K query heads of one KV head into the sublane dim, so
the MXU sees [G, d] x [d, block_k] tiles.

Grid: (batch, kv_heads, seq_blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window: int, cap: float, block_k: int,
            n_blocks: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0, 0]
    k_start = ti * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, bk]
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window:
            mask = jnp.logical_and(mask, (length - 1 - kpos) < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ti == n_blocks - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "cap", "block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, window: int = 0, cap: float = 0.0,
                     block_k: int = 128, interpret: bool):
    """q: [B, H, d]; k/v: [B, K, T, d] slabs (slot t = position t);
    lengths: [B] valid prefix lengths.  Returns [B, H, d]."""
    B, H, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    assert T % block_k == 0, (T, block_k)
    nb = T // block_k
    qg = q.reshape(B, K, G, d)
    len2 = lengths.reshape(B, 1).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=d ** -0.5, window=window, cap=cap, block_k=block_k,
        n_blocks=nb)

    out = pl.pallas_call(
        kernel,
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, ti: (b, 0)),
            pl.BlockSpec((1, 1, G, d), lambda b, h, ti: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ti: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ti: (b, h, ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, h, ti: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
        interpret=interpret,
    )(len2, qg, k, v)
    return out.reshape(B, H, d)
