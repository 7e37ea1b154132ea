"""Pallas TPU ragged paged decode attention: one query token per sequence
against block-table-indexed KV page pools (vLLM/RLAX-style PagedAttention,
FlashDecoding online softmax over the page stream).

The pools are [num_pages, K, page_size, d]; a sequence's KV is scattered
across pages named by its block table row.  One grid step loads one
(page, KV head) block of [page_size, d]: Mosaic tiles the last two block
dimensions (sublane x lane), so the layout keeps them whole.  The block
tables (and true lengths) are *scalar-prefetched* so the per-page DMA
source index is known before the kernel body runs — the grid iterates
pages, and the BlockSpec index map dereferences ``block_tables[b, i]`` to
stream exactly the pages a sequence owns.  Tail pages past a sequence's true length are skipped with
``pl.when`` (no FLOPs, accumulators untouched), so compute scales with the
actual context, not the padded table width.

GQA packs the G = H/K query heads of one KV head into the sublane dim, so
the MXU sees [G, d] x [d, page_size] tiles.

Grid: (batch, kv_heads, n_pages_per_seq).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _live_page(bt, lens, b, ti, ps):
    """Page index for grid step ``ti``, clamped to the row's last LIVE page.

    ``pl.when`` only skips compute — the BlockSpec index map controls the
    DMA.  Clamping tail steps to the last live page keeps the block index
    constant there, which elides the copy: HBM page reads scale with the
    TRUE context length, not the padded table width."""
    last = jnp.maximum((lens[b] - 1) // ps, 0)
    return bt[b, jnp.minimum(ti, last)]


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale: float, cap: float, page_size: int,
            n_pages: int):
    b = pl.program_id(0)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = ti * page_size

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [ps, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, ps]
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

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

    @pl.when(ti == n_pages - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("cap", "scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           cap: float = 0.0, scale=None, interpret: bool):
    """q: [B, H, d]; k_pages/v_pages: [P, K, page_size, d] shared pools;
    block_tables: [B, nb] page ids (position p of sequence b lives at
    (block_tables[b, p // ps], p % ps); pad rows with the garbage page 0);
    lengths: [B] true context lengths (0 allowed => zero output).
    ``scale`` defaults to d**-0.5; the serving path passes 1.0 because the
    model pre-scales q.  Returns [B, H, d]."""
    B, H, d = q.shape
    P, K, ps = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, d)
    bt = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    if scale is None:
        scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, cap=cap, page_size=ps, n_pages=nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block tables + lengths
        grid=(B, K, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, ti, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b, h, ti, bt, ln: (_live_page(bt, ln, b, ti,
                                                              ps), h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b, h, ti, bt, ln: (_live_page(bt, ln, b, ti,
                                                              ps), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda b, h, ti, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(bt, lens, qg, k_pages, v_pages)
    return out.reshape(B, H, d)
