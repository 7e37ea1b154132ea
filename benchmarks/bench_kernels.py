"""Kernel micro-benchmarks: correctness (vs oracle) + modeled TPU roofline
time per configuration.  Wall-clock timing of interpret mode is meaningless
for TPU performance, so we report the kernel's FLOPs/bytes and the v5e
roofline bound alongside the achieved max-abs error."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import interpret_mode
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.hlo_analysis import HBM_BW, PEAK_FLOPS_BF16
from benchmarks.common import emit


def main(quick: bool = False):
    interp = interpret_mode()
    key = jax.random.PRNGKey(0)
    # flash attention (prefill shape, per chip)
    B, H, K, S, d = 1, 8, 2, 1024 if quick else 2048, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, K, S, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, K, S, d), jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, interpret=interp)
    r = ref.flash_attention_ref(q, k, v, causal=True)
    err = float(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32)).max())
    flops = 2.0 * B * H * S * S * d * 2 / 2          # causal half
    byts = (q.size + 2 * k.size + o.size) * 2
    bound = max(flops / PEAK_FLOPS_BF16, byts / HBM_BW)
    emit("kernel/flash_attention/err", err, flops, bound * 1e6)

    # decode attention (serving shape, ragged): modeled bytes follow the
    # TRUE context lengths (what the seq-block-skipping kernel reads), not
    # the full slab capacity
    T = 2048 if quick else 8192
    B2 = 8
    ks = jax.random.split(key, 4)
    q2 = jax.random.normal(ks[0], (B2, H, d), jnp.bfloat16)
    k2 = jax.random.normal(ks[1], (B2, K, T, d), jnp.bfloat16)
    v2 = jax.random.normal(ks[2], (B2, K, T, d), jnp.bfloat16)
    lengths = jax.random.randint(ks[3], (B2,), T // 8, T + 1)
    o2 = decode_attention(q2, k2, v2, lengths, interpret=interp)
    r2 = ref.decode_attention_ref(q2, k2, v2, lengths)
    err2 = float(jnp.abs(o2.astype(jnp.float32)
                         - r2.astype(jnp.float32)).max())
    byts2 = 2 * int(jnp.sum(lengths)) * K * d * 2    # K+V, true lengths, bf16
    bound2 = byts2 / HBM_BW                          # memory-bound
    emit("kernel/decode_attention/err", err2, byts2, bound2 * 1e6)

    # paged decode attention (block-table pools, ragged lengths)
    ps = 64
    nb = T // ps
    P = 1 + B2 * nb
    ks = jax.random.split(key, 5)
    kp = jax.random.normal(ks[0], (P, K, ps, d), jnp.bfloat16)
    vp = jax.random.normal(ks[1], (P, K, ps, d), jnp.bfloat16)
    perm = np.random.RandomState(0).permutation(P - 1)[:B2 * nb] + 1
    bt = jnp.asarray(perm.reshape(B2, nb), jnp.int32)
    plen = jax.random.randint(ks[2], (B2,), 0, T + 1)
    o3 = paged_decode_attention(q2, kp, vp, bt, plen, interpret=interp)
    r3 = ref.paged_decode_attention_ref(q2, kp, vp, bt, plen)
    err3p = float(jnp.abs(o3.astype(jnp.float32)
                          - r3.astype(jnp.float32)).max())
    # pages actually touched (tail pages pl.when-skipped)
    pages_read = int(jnp.sum(-(-plen // ps)))
    byts3 = 2 * pages_read * ps * K * d * 2
    emit("kernel/paged_decode_attention/err", err3p, byts3,
         byts3 / HBM_BW * 1e6)

    # ragged paged prefill (chunk C against a paged prefix): modeled HBM
    # bytes mirror the decode bench — the kernel streams only LIVE prefix
    # pages (pl.when skips pages past each row's offset), so read bytes
    # follow the true prefix lengths; the dense gather it replaces read the
    # full padded nb*ps table per row
    C = 128 if quick else 256
    ks = jax.random.split(key, 6)
    qp = jax.random.normal(ks[0], (B2, C, H, d), jnp.bfloat16)
    kq = jax.random.normal(ks[1], (B2, C, K, d), jnp.bfloat16)
    vq = jax.random.normal(ks[2], (B2, C, K, d), jnp.bfloat16)
    offs = jax.random.randint(ks[3], (B2,), 0, T + 1)
    cls = jax.random.randint(ks[4], (B2,), 1, C + 1)
    o4 = paged_prefill_attention(qp, kq, vq, kp, vp, bt, offs, cls,
                                 interpret=interp)
    r4 = ref.paged_prefill_attention_ref(qp, kq, vq, kp, vp, bt, offs, cls)
    err4 = float(jnp.abs(o4.astype(jnp.float32)
                         - r4.astype(jnp.float32)).max())
    live_pages = int(jnp.sum(-(-offs // ps)))
    byts4 = 2 * (live_pages * ps + B2 * C) * K * d * 2   # K+V: prefix + chunk
    dense_byts4 = 2 * (B2 * nb * ps + B2 * C) * K * d * 2
    emit("kernel/paged_prefill_attention/err", err4, byts4,
         byts4 / HBM_BW * 1e6)
    emit("kernel/paged_prefill_attention/live_vs_padded_bytes",
         byts4 / dense_byts4, byts4, dense_byts4)

    # ssd scan (mamba2-130m geometry)
    b, L, Hh, G, P, N = 1, 512 if quick else 2048, 24, 1, 64, 128
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, L, Hh, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, Hh))).astype(jnp.bfloat16)
    A = -jnp.exp(jax.random.normal(ks[2], (Hh,)) * 0.3)
    B_ = jax.random.normal(ks[3], (b, L, G, N), jnp.bfloat16)
    C_ = jax.random.normal(ks[4], (b, L, G, N), jnp.bfloat16)
    y, st = ssd_scan(x, dt, A, B_, C_, chunk=64, interpret=interp)
    yr, sr = ref.ssd_scan_ref(x, dt, A, B_, C_)
    err3 = float(jnp.abs(y - yr).max() / (jnp.abs(yr).max() + 1e-9))
    chunk = 64
    flops3 = 2.0 * b * L * Hh * (chunk * N + chunk * P + P * N) * 2
    bound3 = max(flops3 / PEAK_FLOPS_BF16,
                 (x.size + B_.size + C_.size + y.size) * 2 / HBM_BW)
    emit("kernel/ssd_scan/rel_err", err3, flops3, bound3 * 1e6)


if __name__ == "__main__":
    main()
