"""Pallas TPU fused dequantization for the weight-transfer plane.

One VPU pass over a quantized leaf: ``out = base + q * scale`` — int8
dequant and delta-accumulate fused, so installing a pulled ``delta-int8``
weight version reads the int8 payload + the resident base weights ONCE and
writes the new weights, instead of materializing an intermediate f32 delta
(2x HBM traffic saved on the accumulate path).  With ``base=None`` it is a
plain int8 dequant (full int8 transfers / cold instances).

Layout: leaves are reshaped to [R, C] with a per-channel (last-dim) f32
scale of width C — the same convention as ``repro.transfer.codec``.  The
grid blocks rows; scale is broadcast from a [1, C] block.  A row block is
capped so its f32 output stays near ``_BLOCK_BYTES``: double-buffered
int8 in + f32 out (+ f32 base) must fit the TPU's scoped VMEM even for
the widest leaves (an [R, 12288] MLP weight at 256 rows would need 30 MiB).

Oracle: ``repro.kernels.ref.dequant_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_BLOCK_BYTES = 2 << 20


def _kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _kernel_acc(q_ref, s_ref, b_ref, o_ref):
    o_ref[...] = (b_ref[...].astype(jnp.float32)
                  + q_ref[...].astype(jnp.float32) * s_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_dequant(q, scale, base=None, *, block_rows: int = 256,
                  interpret: bool):
    """q: [R, C] int8; scale: [C] f32 per-channel; base: [R, C] or None.
    Returns f32 [R, C] = (base or 0) + q * scale."""
    R, C = q.shape
    s2 = scale.reshape(1, C).astype(jnp.float32)
    # rows per block: a multiple of the int8 sublane tile (32) sized to the
    # VMEM budget, or all rows when there are fewer
    br = min(block_rows, R, max(32, _BLOCK_BYTES // (4 * C) // 32 * 32))
    grid = (pl.cdiv(R, br),)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    s_spec = pl.BlockSpec((1, C), lambda i: (0, 0))
    out_shape = jax.ShapeDtypeStruct((R, C), jnp.float32)
    if base is None:
        return pl.pallas_call(
            _kernel, grid=grid, in_specs=[row_spec, s_spec],
            out_specs=row_spec, out_shape=out_shape,
            interpret=interpret)(q, s2)
    return pl.pallas_call(
        _kernel_acc, grid=grid, in_specs=[row_spec, s_spec, row_spec],
        out_specs=row_spec, out_shape=out_shape,
        interpret=interpret)(q, s2, base.astype(jnp.float32))
