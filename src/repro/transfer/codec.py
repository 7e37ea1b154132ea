"""Per-leaf transfer codecs for the weight plane.

A leaf travels as one contiguous payload inside a manifest's encoded
stream:

  * ``none``        raw little-endian bytes of the leaf (bit-exact);
  * ``int8``        per-channel int8 quantization: ``q`` (leaf.size bytes)
                    followed by a f32 scale per last-dim channel — 2x+
                    compression, error <= scale/2 per element;
  * ``delta-int8``  int8 quantization of ``leaf - base`` where ``base`` is
                    the receiver's resident version of the leaf.  Error is
                    <= scale_delta/2 per element PER HOP and accumulates
                    additively across consecutive delta installs (the
                    runtime refreshes with a full int8 pull whenever the
                    receiver's base version is unknown/expired, which
                    bounds the chain).

Decoding the int8 codecs routes through the fused Pallas kernel
(``repro.kernels.dequant``) when ``use_pallas=True`` — dequant and
delta-accumulate in one device pass — with the plain-numpy math as the
host fallback.  Quantization convention: leaves are viewed as
[rows, last_dim] with a per-channel scale; 1-D/0-D leaves quantize as a
[n, 1] column with one global scale.

The same codecs carry KV-page migrations (``chunkstore.build_kv_manifest``):
there each manifest leaf is ONE page ``[K, page_size, dh]``, so the int8
scales are per page x head-dim channel — error <= scale/2 per element,
bounded by that page's own magnitude (``tests/test_kv_migration.py``
checks the bound against the ``kernels.ref`` dequant oracle).
"""

from __future__ import annotations

import numpy as np

COMPRESSION_FACTOR = {"none": 1.0, "int8": 0.5, "delta-int8": 0.25}


def quantize_int8(arr: np.ndarray):
    a = np.asarray(arr, np.float32)
    flat = a.reshape(-1, a.shape[-1]) if a.ndim > 1 else a.reshape(1, -1)
    scale = np.abs(flat).max(axis=0) / 127.0 + 1e-12
    q = np.clip(np.round(flat / scale), -127, 127).astype(np.int8)
    return q.reshape(a.shape if a.ndim > 1 else (-1,)), scale


def dequantize_int8(q, scale, shape):
    f = q.astype(np.float32).reshape(-1, q.shape[-1]) * scale
    return f.reshape(shape)


def _rows(a: np.ndarray) -> np.ndarray:
    """Channel view for quantization: [rows, last_dim] for >=2-D leaves;
    1-D/0-D leaves become a [n, 1] column with ONE global scale (a
    per-element scale would make biases travel LARGER than raw)."""
    a = np.asarray(a)
    return a.reshape(-1, a.shape[-1]) if a.ndim > 1 else a.reshape(-1, 1)


def encode_leaf(arr, codec: str, base=None) -> bytes:
    a = np.asarray(arr)
    if codec == "none":
        return a.tobytes()
    if codec == "delta-int8":
        a = a.astype(np.float32) - np.asarray(base, np.float32)
    # one quantizer, channel view fixed by _rows (2-D in, so the legacy
    # 1-D per-element-scale behavior of quantize_int8 never applies here)
    q, scale = quantize_int8(_rows(a.astype(np.float32)))
    return q.tobytes() + np.asarray(scale, np.float32).tobytes()


def decode_leaf(payload: bytes, spec, base=None, use_pallas: bool = False):
    """Decode one leaf payload back to ``spec.shape``/``spec.dtype``.

    ``spec`` is a ``chunkstore.LeafSpec``; ``base`` is the receiver's
    resident leaf (required iff ``spec.codec == 'delta-int8'``).
    """
    shape = tuple(spec.shape)
    dtype = np.dtype(spec.dtype)
    if spec.codec == "none":
        return np.frombuffer(payload, dtype=dtype).reshape(shape).copy()
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    q = np.frombuffer(payload[:n], np.int8)
    scale = np.frombuffer(payload[n:], np.float32)
    C = shape[-1] if len(shape) > 1 else 1
    q2 = q.reshape(-1, C)
    base2 = None
    if spec.codec == "delta-int8":
        base2 = _rows(np.asarray(base, np.float32))
    if use_pallas:
        import jax.numpy as jnp

        from repro.kernels.dequant import fused_dequant
        from repro.kernels.ops import interpret_mode
        out = np.asarray(fused_dequant(
            jnp.asarray(q2), jnp.asarray(scale),
            jnp.asarray(base2) if base2 is not None else None,
            interpret=interpret_mode()))
    else:
        out = q2.astype(np.float32) * scale[None, :]
        if base2 is not None:
            out = out + base2
    return out.reshape(shape).astype(dtype, copy=False)
