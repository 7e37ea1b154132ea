"""Pallas TPU ragged paged decode attention: one query token per sequence
against block-table-indexed KV page pools (vLLM/RLAX-style PagedAttention,
FlashDecoding online softmax over the page stream).

The pools are [num_pages, K, page_size, d]; a sequence's KV is scattered
across pages named by its block table row.  The pools stay in HBM
(``memory_space=pl.ANY``) and the kernel moves them itself: the grid runs
over rows only, and each row loops over ``ceil(len / (ppb * page_size))``
blocks of ``ppb`` pages.  A block is fetched with one async copy per page
of the whole ``[K, page_size, d]`` slab (all KV heads) into a VMEM buffer
of ``[ppb, K, page_size, d]``, named by the scalar-prefetched block table.
The buffers are doubled, so the next block's copies are in flight while
the current block computes.  Only live pages are copied: pages past the
row's last live page issue no copy, and a row of length 0 costs one empty
grid step, so HBM reads and loop trips scale with the true context, not
with the batch or the padded table width.

``ppb`` comes from the shapes alone: about 256 tokens a block
(``256 // page_size`` pages, at most ``nb``).  The four page buffers take
``4 * 256 * K * d * itemsize`` bytes of VMEM: 8 MiB for the widest
configured pool (16 KV heads of 128, f32), half the default scoped limit.

Per block and KV head, GQA packs the G = H/K query heads into the sublane
dim, so the MXU sees [G, d] x [d, ppb * page_size] tiles; the online-softmax
state (m, l, acc) is kept per (KV head, query head) in VMEM scratch, in
float32.  Positions at or past the row's length are masked out of both the
scores and V, so stale buffer slots cannot reach the result.

Grid: (batch,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
BLOCK_TOKENS = 256          # tokens per streamed block, rounded to pages


def _kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
            m_scr, l_scr, acc_scr, *, scale: float, cap: float,
            page_size: int, ppb: int, n_pages: int):
    b = pl.program_id(0)
    ps = page_size
    bk = ppb * ps
    K = k_buf.shape[2]
    length = jnp.minimum(len_ref[b], n_pages * ps)
    live_pages = (length + ps - 1) // ps
    n_blocks = (length + bk - 1) // bk

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # Pages and heads are loops, not unrolled Python: every process traces
    # and lowers the kernel again for each program that holds it, with the
    # compile cache or without, and an unrolled body multiplies that cost.
    def copies(blk, slot, start):
        # one copy per live page of the block, K and V; both ends loop over
        # the same pages, so every started copy is waited for exactly once
        first = blk * ppb

        def page(j, carry):
            src_page = bt_ref[b, first + j] if start else 0
            for n, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(src.at[src_page], dst.at[slot, j],
                                           sem.at[n, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, live_pages - first), page, 0)

    @pl.when(n_blocks > 0)
    def _():
        copies(0, 0, start=True)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copies(blk + 1, 1 - slot, start=True)

        copies(blk, slot, start=False)
        k0 = blk * bk
        valid = (k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)) < length
        valid_col = (k0 + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                     ) < length

        def head(h, c):
            q = q_ref[0, h].astype(jnp.float32)                    # [G, d]
            k = k_buf[slot, :, h].astype(jnp.float32).reshape(bk, -1)
            v = v_buf[slot, :, h].astype(jnp.float32).reshape(bk, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale       # [G, bk]
            if cap:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(valid, s, NEG_INF)
            v = jnp.where(valid_col, v, 0.0)

            m_prev = m_scr[h]                                      # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new
            return c

        jax.lax.fori_loop(0, K, head, 0)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)

    l = l_scr[...]
    safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


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
    K, ps = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    G = H // K
    ppb = min(nb, max(1, BLOCK_TOKENS // ps))
    qg = q.reshape(B, K, G, d)
    bt = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    if scale is None:
        scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, cap=cap, page_size=ps, ppb=ppb, n_pages=nb)
    row = pl.BlockSpec((1, K, G, d), lambda b, bt, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block tables + lengths
        grid=(B,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, K, ps, d), k_pages.dtype),
            pltpu.VMEM((2, ppb, K, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K or V, buffer slot)
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, d), jnp.float32),
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
