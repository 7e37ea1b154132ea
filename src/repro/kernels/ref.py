"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0, cap=0.0):
    """q: [B, H, S, d]; k/v: [B, K, S, d] -> [B, H, S, d] (f32 math)."""
    B, H, S, d = q.shape
    K = k.shape[1]
    G = H // K
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if cap:
        s = cap * jnp.tanh(s / cap)
    pos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return o.astype(q.dtype)


def decode_attention_ref(q, k, v, lengths, *, window=0, cap=0.0):
    """q: [B, H, d]; k/v: [B, K, T, d]; lengths: [B] valid prefix lengths.

    Slot t of the cache holds absolute position t (slab layout).
    Returns [B, H, d].
    """
    B, H, d = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=1)
    s = jnp.einsum("bhd,bhtd->bht", qf, kf)
    if cap:
        s = cap * jnp.tanh(s / cap)
    t = jnp.arange(T)[None, :]
    mask = t < lengths[:, None]
    if window:
        mask &= (lengths[:, None] - 1 - t) < window
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bhtd->bhd", p, vf).astype(q.dtype)


def _gather_paged(pages, block_tables):
    """[P, K, ps, d] pool + [B, nb] tables -> dense [B, nb*ps, K, d] in table
    order (gathered slot i holds absolute position i)."""
    g = pages[block_tables]                      # [B, nb, K, ps, d]
    B, nb, K, ps, d = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * ps, K, d)


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                               cap=0.0):
    """Oracle for the paged kernel: gather pages into a dense slab and run
    ``decode_attention_ref``.

    q: [B, H, d]; k_pages/v_pages: [P, K, ps, d]; block_tables: [B, nb];
    lengths: [B].  Gathered slot i holds absolute position i (pages are
    table-ordered).  Rows with length 0 return exactly zero (they have no
    attendable context; the kernel's empty accumulator emits zeros).
    """
    k = _gather_paged(k_pages, block_tables).transpose(0, 2, 1, 3)
    v = _gather_paged(v_pages, block_tables).transpose(0, 2, 1, 3)
    out = decode_attention_ref(q, k, v, lengths, cap=cap)
    return jnp.where((lengths > 0)[:, None, None], out,
                     jnp.zeros_like(out))


def paged_prefill_attention_ref(q, k, v, k_pages, v_pages, block_tables,
                                offsets, chunk_lens, *, cap=0.0, scale=None):
    """Oracle for the ragged paged PREFILL kernel: gather the prefix pages
    dense, concat the chunk K/V, mask, softmax.

    q: [B, C, H, d] (unscaled unless ``scale`` given); k/v: [B, C, K, d];
    k_pages/v_pages: [P, K, ps, d]; block_tables: [B, nb]; offsets /
    chunk_lens: [B].  Query i of row b attends prefix positions < offsets[b]
    plus chunk positions j <= i with j < chunk_lens[b].  Rows with offset 0
    AND chunk_len 0 return exact zeros (matching the kernel's empty
    accumulator).
    """
    B, C, H, d = q.shape
    K = k.shape[2]
    G = H // K
    nb, ps = block_tables.shape[1], k_pages.shape[2]
    T = nb * ps
    if scale is None:
        scale = d ** -0.5
    k_pre = _gather_paged(k_pages, block_tables)
    v_pre = _gather_paged(v_pages, block_tables)
    kk = jnp.concatenate([k_pre, k], axis=1).astype(jnp.float32)  # [B,T+C,K,d]
    vv = jnp.concatenate([v_pre, v], axis=1).astype(jnp.float32)
    kk = jnp.repeat(kk, G, axis=2)                                # [B,T+C,H,d]
    vv = jnp.repeat(vv, G, axis=2)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bchd,bthd->bhct", qf, kk)
    if cap:
        s = cap * jnp.tanh(s / cap)
    qpos = offsets[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [B, C]
    kvpos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T)),
         qpos], axis=1)                                              # [B,T+C]
    valid = jnp.concatenate(
        [jnp.arange(T, dtype=jnp.int32)[None] < offsets[:, None],
         jnp.arange(C, dtype=jnp.int32)[None] < chunk_lens[:, None]], axis=1)
    mask = valid[:, None, :] & (kvpos[:, None, :] <= qpos[:, :, None])
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhct,bthd->bchd", p, vv)
    empty = (offsets == 0) & (chunk_lens == 0)
    out = jnp.where(empty[:, None, None, None], 0.0, out)
    return out.astype(q.dtype)


def dequant_ref(q, scale, base=None):
    """Oracle for the fused dequant/delta-accumulate kernel.

    q: [R, C] int8; scale: [C] f32 (per last-dim channel); base: [R, C] or
    None.  Returns f32 [R, C] = (base or 0) + q * scale.
    """
    out = q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    if base is not None:
        out = out + base.astype(jnp.float32)
    return out


def ssd_scan_ref(x, dt, A, B, C, *, chunk=None):
    """Sequential SSD recurrence oracle (mathematically exact, O(L) steps).

    x: [b, L, H, P]; dt: [b, L, H]; A: [H] (negative); B/C: [b, L, G, N].
    Returns (y [b, L, H, P], final_state [b, H, P, N]).
    """
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, Bt, Ct = inp            # [b,H,P],[b,H],[b,H,N],[b,H,N]
        dA = jnp.exp(dtt * Af[None, :])
        state = (state * dA[..., None, None]
                 + jnp.einsum("bhn,bhp->bhpn", Bt, xt * dtt[..., None]))
        y = jnp.einsum("bhn,bhpn->bhp", Ct, state)
        return state, y

    state0 = jnp.zeros((b, H, P, N), jnp.float32)
    xs = (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
          Bf.swapaxes(0, 1), Cf.swapaxes(0, 1))
    final, ys = jax.lax.scan(step, state0, xs)
    return ys.swapaxes(0, 1), final
