"""Pallas TPU ragged paged PREFILL attention: one chunk of C query tokens per
sequence against its paged prefix plus the chunk's own causal K/V (the missing
sibling of ``kernels.paged_attention`` — together they retire the dense
``gather_pages`` + concat + ``[B, C, T+C]`` mask from the serving hot path).

The KV stream a query block sees is two-phase:

  * ``nb`` prefix pages, DMA-gathered through the scalar-prefetched block
    table exactly like the decode kernel; pages whose first position is at or
    past the row's true ``offset`` are skipped with ``pl.when`` (no FLOPs, no
    accumulator update), and the partial boundary page is tail-masked with
    ``kpos < offset`` — HBM reads scale with the TRUE prefix length, not the
    padded table width;
  * the in-chunk K/V blocks (the chunk attends to itself causally BEFORE its
    KV is written to pages), with blocks strictly above the causal diagonal
    skipped and the block mask ``kidx <= qidx & kidx < chunk_len`` handling
    right-padded rows.

Online softmax (flash-style m/l/acc scratch) runs across both phases, so the
two streams fuse into one softmax — no concatenated [T+C] score row ever
materializes.  GQA packs the G = H/K query heads of one KV head next to the
``qb`` query rows, so the MXU sees [qb, G, d] x [d, kk] tiles.

Grid: (batch, kv_heads, n_q_blocks, nb + n_chunk_blocks), KV stream innermost.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _kernel(bt_ref, off_ref, cl_ref, q_ref, kc_ref, vc_ref, kp_ref, vp_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float, cap: float,
            page_size: int, n_pages: int, qb: int, ckb: int, n_kv: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ti = pl.program_id(3)

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    offset = off_ref[b]
    chunk_len = cl_ref[b]

    def _accumulate(s, vblk):
        """s: [qb, G, kk] masked scores; vblk: [kk, d] f32."""
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        # an all-masked score row leaves m_new at NEG_INF; exp(s - m_new)
        # would then be exp(0) = 1 per masked entry — zero them explicitly
        # (rows with chunk_len 0 process diagonal blocks fully masked)
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=2)
        acc_scr[...] = (acc_scr[...] * corr[..., None]
                        + jax.lax.dot_general(
                            p, vblk, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    def _scores(q, kblk):
        s = jax.lax.dot_general(
            q, kblk, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [qb, G, kk]
        if cap:
            s = cap * jnp.tanh(s / cap)
        return s

    q = q_ref[0, 0].astype(jnp.float32) * scale           # [qb, G, d]

    # ---- phase 1: prefix pages (skip pages at/past the true offset) ---- #
    @pl.when((ti < n_pages) & (ti * page_size < offset))
    def _prefix():
        k = kp_ref[0, 0].astype(jnp.float32)              # [ps, d]
        v = vp_ref[0, 0].astype(jnp.float32)
        s = _scores(q, k)
        kpos = ti * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # prefix positions precede every chunk query — only the row's true
        # prefix length masks (no causal test needed)
        s = jnp.where(kpos < offset, s, NEG_INF)
        _accumulate(s, v)

    # ---- phase 2: in-chunk causal blocks (skip above the diagonal) ---- #
    ci = ti - n_pages
    @pl.when((ti >= n_pages) & (ci * ckb <= qi * qb + qb - 1))
    def _chunk():
        k = kc_ref[0, 0].astype(jnp.float32)              # [ckb, d]
        v = vc_ref[0, 0].astype(jnp.float32)
        s = _scores(q, k)
        kidx = ci * ckb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qidx = qi * qb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where((kidx <= qidx) & (kidx < chunk_len), s, NEG_INF)
        _accumulate(s, v)

    @pl.when(ti == n_kv - 1)
    def _emit():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe[..., None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("cap", "scale", "interpret"))
def paged_prefill_attention(q, k, v, k_pages, v_pages, block_tables, offsets,
                            chunk_lens, *, cap: float = 0.0,
                            scale: Optional[float] = None, interpret: bool):
    """q: [B, C, H, d] roped queries (scaled by ``scale``, default d**-0.5);
    k/v: [B, C, K, d] the chunk's own roped K/V (NOT yet in the pool);
    k_pages/v_pages: [P, K, page_size, d] shared pools holding each row's
    prefix; block_tables: [B, nb] page ids (pad with the garbage page 0);
    offsets: [B] true prefix lengths already in the pool (0 allowed);
    chunk_lens: [B] valid tokens in this right-padded chunk.

    Query i of row b sits at absolute position offsets[b] + i and attends the
    row's prefix (positions < offsets[b]) plus chunk positions j <= i with
    j < chunk_lens[b].  Rows with offset 0 and chunk_len 0 emit exact zeros.
    Returns [B, C, H, d].
    """
    B, C, H, d = q.shape
    P, K, ps = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    G = H // K
    if scale is None:
        scale = d ** -0.5
    # C is kernel-tile bucketed by the engine (multiples of 128); arbitrary
    # direct callers fall back to one single-block grid step
    qb = 128 if C % 128 == 0 else C
    ckb = qb
    nqb, ncb = C // qb, C // ckb
    n_kv = nb + ncb

    qg = (q.reshape(B, C, K, G, d).transpose(0, 2, 1, 3, 4))   # [B,K,C,G,d]
    kc = k.transpose(0, 2, 1, 3)                               # [B,K,C,d]
    vc = v.transpose(0, 2, 1, 3)
    bt = block_tables.astype(jnp.int32)
    offs = offsets.astype(jnp.int32)
    cls = chunk_lens.astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, cap=cap, page_size=ps, n_pages=nb, qb=qb,
        ckb=ckb, n_kv=n_kv)

    def _page_idx(b, h, qi, ti, bt, off, cl):
        # pl.when only skips COMPUTE — the index map controls the DMA.
        # Clamp to the row's last LIVE page (and stay there through the
        # chunk phase): a block index unchanged from the previous grid step
        # elides the copy, so HBM page reads really do stop at the true
        # prefix length instead of streaming the padded table width.
        last_live = jnp.maximum((off[b] - 1) // ps, 0)
        i = jnp.minimum(jnp.minimum(ti, nb - 1), last_live)
        return (bt[b, i], h, 0, 0)

    def _chunk_idx(b, h, qi, ti, bt, off, cl):
        return (b, h, jnp.maximum(ti - nb, 0), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # block tables, offsets, chunk_lens
        grid=(B, K, nqb, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, qb, G, d),
                         lambda b, h, qi, ti, bt, off, cl: (b, h, qi, 0, 0)),
            pl.BlockSpec((1, 1, ckb, d), _chunk_idx),
            pl.BlockSpec((1, 1, ckb, d), _chunk_idx),
            pl.BlockSpec((1, 1, ps, d), _page_idx),
            pl.BlockSpec((1, 1, ps, d), _page_idx),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, qb, G, d),
            lambda b, h, qi, ti, bt, off, cl: (b, h, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb, G), jnp.float32),
            pltpu.VMEM((qb, G), jnp.float32),
            pltpu.VMEM((qb, G, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, C, G, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(bt, offs, cls, qg, kc, vc, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, H, d)
