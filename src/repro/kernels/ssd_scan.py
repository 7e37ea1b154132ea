"""Pallas TPU Mamba-2 SSD chunked scan (state-space duality,
arXiv:2405.21060).

TPU adaptation: the intra-chunk quadratic part is three MXU matmuls
([c,N]x[N,c] scores, [c,c]x[c,P] diag output, [N,c]x[c,P] chunk state); the
inter-chunk recurrence carries the [P,N] state in VMEM scratch across the
sequential chunk grid dimension — the kernel never materialises the [L,L]
semiseparable matrix.

Grid: (batch, heads, n_chunks).  B/C index maps fold the SSD group
(h // rep) so grouped B/C are read without host-side repetition.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr, *,
            chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # [c, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # [c]
    A = a_ref[0].astype(jnp.float32)                 # scalar
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)       # [c, N]
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)       # [c, N]

    dA = dt * A                                      # [c]
    cum = jnp.cumsum(dA)                             # [c]
    # L[s,t] = exp(cum[s] - cum[t]) for s >= t else 0
    seg = cum[:, None] - cum[None, :]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    Lmat = jnp.where(tri, jnp.exp(seg), 0.0)

    xdt = x * dt[:, None]                            # [c, P]
    scores = jax.lax.dot_general(                    # [c, c] = C @ B^T
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(                    # [c, P]
        scores * Lmat, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    state = state_scr[...]                           # [P, N]
    y_off = jax.lax.dot_general(                     # [c, P] = C @ state^T
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum)[:, None]

    decay_out = jnp.exp(cum[-1] - cum)               # [c]
    chunk_state = jax.lax.dot_general(               # [P, N] = xdt^T @ (B*decay)
        xdt, Bm * decay_out[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(cum[-1]) + chunk_state

    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        st_ref[0, 0] = state_scr[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, interpret: bool):
    """x: [b, L, H, P]; dt: [b, L, H]; A: [H]; B/C: [b, L, G, N].

    Returns (y [b, L, H, P] f32, final_state [b, H, P, N] f32).
    """
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)

    y, st = pl.pallas_call(
        kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, ci: (bi, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, h, ci: (bi, ci, h)),
            pl.BlockSpec((1,), lambda bi, h, ci: (h,)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bi, h, ci: (bi, ci, h // rep, 0)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bi, h, ci: (bi, ci, h // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, ci: (bi, ci, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, L, H, P), jnp.float32),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y, st
