"""jit'd wrappers: model-layout entry points with a pallas/ref switch.

The model keeps [B, S, H, d] activations; the kernels use head-major
[B, H, S, d].  Every caller of a Pallas kernel takes ``interpret`` from
:func:`interpret_mode`, the one place that decides it from the backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: True on the CPU (tests
    and examples), False on a TPU (Mosaic-compiled).  Any other backend has
    no kernel path and raises, rather than interpreting on an accelerator."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


def attention_bshd(q, k, v, *, causal=True, window=0, cap=0.0,
                   use_pallas=False, block_q=128, block_k=128):
    """q: [B,S,H,d] (unscaled), k/v: [B,S,K,d] -> [B,S,H,d]."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    if use_pallas:
        o = flash_attention(qt, kt, vt, causal=causal, window=window,
                            cap=cap, block_q=block_q, block_k=block_k,
                            interpret=interpret_mode())
    else:
        o = ref.flash_attention_ref(qt, kt, vt, causal=causal,
                                    window=window, cap=cap)
    return o.swapaxes(1, 2)


def decode_bshd(q, k_cache, v_cache, lengths, *, window=0, cap=0.0,
                use_pallas=False, block_k=128):
    """q: [B,1,H,d]; slab caches [B,T,K,d]; lengths [B] -> [B,1,H,d]."""
    qt = q[:, 0]
    kt = k_cache.swapaxes(1, 2)
    vt = v_cache.swapaxes(1, 2)
    if use_pallas:
        o = decode_attention(qt, kt, vt, lengths, window=window, cap=cap,
                             block_k=block_k, interpret=interpret_mode())
    else:
        o = ref.decode_attention_ref(qt, kt, vt, lengths, window=window,
                                     cap=cap)
    return o[:, None]


def ssd(x, dt, A, B, C, *, chunk=64, use_pallas=False):
    if use_pallas:
        return ssd_scan(x, dt, A, B, C, chunk=chunk,
                        interpret=interpret_mode())
    return ref.ssd_scan_ref(x, dt, A, B, C)
