"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle.
(Deliverable c: kernel allclose.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,d,causal,window,cap", [
    (2, 4, 2, 256, 64, True, 0, 0.0),       # GQA causal
    (1, 4, 4, 256, 64, True, 64, 0.0),      # MHA sliding-window
    (2, 2, 1, 128, 32, True, 0, 50.0),      # MQA + softcap (gemma2)
    (1, 8, 2, 256, 128, False, 0, 0.0),     # encoder (bidirectional)
    (1, 2, 2, 512, 64, True, 128, 30.0),    # window + softcap
])
def test_flash_attention(B, H, K, S, d, causal, window, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, d), dtype)
    k = jax.random.normal(ks[1], (B, K, S, d), dtype)
    v = jax.random.normal(ks[2], (B, K, S, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                          block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,T,d,window,cap", [
    (2, 4, 2, 256, 64, 0, 0.0),
    (1, 8, 8, 256, 64, 64, 0.0),
    (3, 4, 1, 128, 128, 0, 30.0),
    (2, 16, 4, 512, 64, 0, 0.0),
])
def test_decode_attention(B, H, K, T, d, window, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    k = jax.random.normal(ks[1], (B, K, T, d), dtype)
    v = jax.random.normal(ks[2], (B, K, T, d), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    out = decode_attention(q, k, v, lengths, window=window, cap=cap,
                           block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,ps,nb,d,cap", [
    (4, 4, 2, 16, 8, 64, 0.0),               # GQA
    (2, 8, 8, 32, 4, 64, 0.0),               # MHA
    (3, 4, 1, 8, 16, 128, 30.0),             # MQA + softcap
])
def test_paged_decode_attention(B, H, K, ps, nb, d, cap, dtype):
    """Ragged paged kernel vs the gather-then-dense oracle, including
    length 0, lengths on a page boundary, and lengths spanning pages."""
    P = 1 + B * nb                             # page 0 = garbage
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    kp = jax.random.normal(ks[1], (P, K, ps, d), dtype)
    vp = jax.random.normal(ks[2], (P, K, ps, d), dtype)
    perm = np.random.RandomState(3).permutation(P - 1)[:B * nb] + 1
    bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    # first rows pin the edge cases, the rest are random ragged lengths
    edge = [0, ps, ps + 1, nb * ps]
    lens = np.asarray(
        (edge + list(np.random.RandomState(4).randint(1, nb * ps + 1,
                                                      size=B)))[:B],
        np.int32)
    lengths = jnp.asarray(lens)
    out = paged_decode_attention(q, kp, vp, bt, lengths, cap=cap,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths, cap=cap)
    tol = 1e-2 if dtype == jnp.bfloat16 else TOL[dtype]
    err = float(jnp.abs(out.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    assert err <= tol, err
    if lens[0] == 0:
        assert float(jnp.abs(out[0]).max()) == 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,C,H,K,ps,nb,d,cap", [
    (4, 32, 4, 2, 8, 6, 16, 0.0),            # GQA, single q block
    (2, 128, 4, 4, 16, 4, 32, 0.0),          # MHA, one 128-tile
    (3, 256, 2, 1, 8, 8, 32, 30.0),          # MQA + softcap, 2 q blocks
])
def test_paged_prefill_attention(B, C, H, K, ps, nb, d, cap, dtype):
    """Ragged paged prefill kernel vs the gather+concat oracle: offsets at
    0 / mid-page / page boundary / full table, chunk_lens at 0 / full /
    ragged tails."""
    P = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(ks[0], (B, C, H, d), dtype)
    k = jax.random.normal(ks[1], (B, C, K, d), dtype)
    v = jax.random.normal(ks[2], (B, C, K, d), dtype)
    kp = jax.random.normal(ks[3], (P, K, ps, d), dtype)
    vp = jax.random.normal(ks[4], (P, K, ps, d), dtype)
    perm = np.random.RandomState(2).permutation(P - 1)[:B * nb] + 1
    bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    offs = np.asarray(([0, ps // 2 + 1, ps, nb * ps])[:B], np.int32)
    cls = np.asarray(([0, C, C - 3, max(C // 2, 1)])[:B], np.int32)
    out = paged_prefill_attention(q, k, v, kp, vp, bt, jnp.asarray(offs),
                                  jnp.asarray(cls), cap=cap, interpret=True)
    want = ref.paged_prefill_attention_ref(q, k, v, kp, vp, bt,
                                           jnp.asarray(offs),
                                           jnp.asarray(cls), cap=cap)
    tol = 1e-2 if dtype == jnp.bfloat16 else TOL[dtype]
    err = float(jnp.abs(out.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    assert err <= tol, err
    if offs[0] == 0 and cls[0] == 0:
        assert float(jnp.abs(out[0]).max()) == 0.0


def test_paged_prefill_matches_dense_model_oracle():
    """Kernel == attention_paged_prefill (the dense serving oracle) on the
    valid chunk positions, with the model's pre-scaled queries."""
    from repro.models.attention import attention_paged_prefill
    B, C, H, K, ps, nb, d = 3, 64, 4, 2, 8, 5, 16
    P = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(23), 5)
    q = jax.random.normal(ks[0], (B, C, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, C, K, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, C, K, d), jnp.float32)
    kp = jax.random.normal(ks[3], (P, K, ps, d), jnp.float32)
    vp = jax.random.normal(ks[4], (P, K, ps, d), jnp.float32)
    perm = np.random.RandomState(6).permutation(P - 1)[:B * nb] + 1
    bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    offs = jnp.asarray([0, 7, 3 * ps], jnp.int32)
    cls = jnp.asarray([C, C - 9, C // 2], jnp.int32)
    qs = q * (d ** -0.5)
    out = paged_prefill_attention(qs, k, v, kp, vp, bt, offs, cls,
                                  scale=1.0, interpret=True)
    want = attention_paged_prefill(qs, k, v, kp, vp, bt, offs, cls, cap=0.0)
    valid = (jnp.arange(C)[None] < cls[:, None])[:, :, None, None]
    err = float(jnp.abs((out - want) * valid).max())
    assert err <= 2e-5, err


def test_paged_matches_dense_decode_attention():
    """Paged layout == dense slab layout for the same logical KV."""
    B, H, K, ps, nb, d = 2, 4, 2, 8, 8, 32
    T = ps * nb
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (B, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, K, T, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, K, T, d), jnp.float32)
    lengths = jnp.asarray([T // 2 + 3, T], jnp.int32)
    # scatter the dense slab into pages following a block table
    perm = np.random.RandomState(7).permutation(B * nb) + 1
    bt = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    kp = jnp.zeros((1 + B * nb, K, ps, d), jnp.float32)
    vp = jnp.zeros_like(kp)
    kt = k.reshape(B, K, nb, ps, d).transpose(0, 2, 1, 3, 4)
    vt = v.reshape(B, K, nb, ps, d).transpose(0, 2, 1, 3, 4)
    kp = kp.at[bt.reshape(-1)].set(kt.reshape(B * nb, K, ps, d))
    vp = vp.at[bt.reshape(-1)].set(vt.reshape(B * nb, K, ps, d))
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,L,H,G,P,N,chunk", [
    (2, 128, 4, 1, 64, 32, 32),
    (1, 256, 8, 2, 32, 64, 64),
    (2, 64, 2, 2, 16, 16, 16),
    (1, 128, 24, 1, 64, 128, 64),            # mamba2-130m geometry
])
def test_ssd_scan(b, L, H, G, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (b, L, G, N), dtype)
    C_ = jax.random.normal(ks[4], (b, L, G, N), dtype)
    y, st = ssd_scan(x, dt, A, B_, C_, chunk=chunk, interpret=True)
    yr, sr = ref.ssd_scan_ref(x, dt, A, B_, C_)
    scale = float(jnp.abs(yr).max()) + 1e-6
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    assert float(jnp.abs(y - yr).max()) / scale < tol
    sscale = float(jnp.abs(sr).max()) + 1e-6
    assert float(jnp.abs(st - sr).max()) / sscale < tol


def test_ssd_scan_matches_model_path():
    """Kernel, ref oracle, and the model's chunked scan agree pairwise."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, L, H, G, P, N = 1, 128, 4, 1, 32, 16
    x = jax.random.normal(ks[0], (b, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (b, L, G, N), jnp.float32)
    C_ = jax.random.normal(ks[4], (b, L, G, N), jnp.float32)
    y1, s1 = ssd_scan(x, dt, A, B_, C_, chunk=32, interpret=True)
    y2, s2 = ssd_chunked(x, dt, A, B_, C_, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=1e-4, rtol=1e-4)


def test_model_attention_pallas_path():
    """ModelRuntime(use_pallas=True) forward == jnp forward (interpret)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import CPU_RT, forward, init_params
    cfg = get_config("qwen2-7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                              cfg.vocab_size)
    rt_p = dataclasses.replace(CPU_RT, use_pallas=True)
    a = forward(params, cfg, CPU_RT, tokens=toks, mode="train")["hidden"]
    b = forward(params, cfg, rt_p, tokens=toks, mode="train")["hidden"]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("R,C,with_base,block_rows", [
    (8, 16, False, 8),        # tiny leaf, no base (full int8 pull)
    (100, 37, True, 32),      # ragged rows, delta-accumulate
    (256, 128, True, 64),     # lane-aligned
    (1, 5, False, 8),         # 1-D leaf viewed as a single row
])
def test_dequant_kernel(R, C, with_base, block_rows):
    from repro.kernels.dequant import fused_dequant
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-127, 128, (R, C)), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-4, 1e-2, (C,)), jnp.float32)
    base = (jnp.asarray(rng.randn(R, C), jnp.float32)
            if with_base else None)
    out = fused_dequant(q, scale, base, block_rows=block_rows,
                        interpret=True)
    want = ref.dequant_ref(q, scale, base)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", RuntimeError)])
def test_interpret_mode_follows_backend(monkeypatch, backend, want):
    """Interpret mode only on the CPU; a backend with no kernel path raises
    rather than silently interpreting on an accelerator."""
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is want
