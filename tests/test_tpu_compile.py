"""Ahead-of-time compiles of the serving, weight-swap and training
attention kernels for a described TPU v5e at Qwen3-8B widths (H=32, K=8,
head_dim=128, d_model 4096, d_ff 12288).

Interpret mode cannot see Mosaic's refusals (block tiling, VMEM); the TPU
compiler installed alongside JAX can, without a chip.  Nothing runs: each
test lowers and compiles one kernel for the first device of a ``v5e:2x2``
topology and checks that the kernel survives into the HLO as a
``tpu_custom_call``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dequant import fused_dequant
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_prefill import paged_prefill_attention

# Qwen3-8B widths; serving geometry of the engine's pools
H, K, D, D_MODEL, D_FF = 32, 8, 128, 4096, 12288
PAGE_SIZE = 16
B, NB, C = 8, 64, 256
N_PAGES = 1 + B * NB
ENGINE_POOL_DTYPE = jnp.float32     # InferenceEngine allocates f32 pools


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [ENGINE_POOL_DTYPE, jnp.bfloat16],
                         ids=["pool_dtype", "bf16"])
def test_paged_decode_compiles_for_v5e(one_chip, dtype):
    args = (_spec((B, H, D), dtype, one_chip),
            _spec((N_PAGES, K, PAGE_SIZE, D), dtype, one_chip),
            _spec((N_PAGES, K, PAGE_SIZE, D), dtype, one_chip),
            _spec((B, NB), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: paged_decode_attention(*a, scale=1.0,
                                                   interpret=False))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("dtype", [ENGINE_POOL_DTYPE, jnp.bfloat16],
                         ids=["pool_dtype", "bf16"])
def test_paged_prefill_compiles_for_v5e(one_chip, dtype):
    args = (_spec((B, C, H, D), dtype, one_chip),
            _spec((B, C, K, D), dtype, one_chip),
            _spec((B, C, K, D), dtype, one_chip),
            _spec((N_PAGES, K, PAGE_SIZE, D), dtype, one_chip),
            _spec((N_PAGES, K, PAGE_SIZE, D), dtype, one_chip),
            _spec((B, NB), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: paged_prefill_attention(*a, scale=1.0,
                                                    interpret=False))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("rows,cols,delta", [
    (4096, 4096, False),
    # the stacked MLP weight of 8 layers, delta-accumulated: the widest
    # leaf a weight swap dequantizes (VMEM-bound block sizing)
    (8 * D_MODEL, D_FF, True),
], ids=["4096x4096", "mlp_delta"])
def test_fused_dequant_compiles_for_v5e(one_chip, rows, cols, delta):
    args = [_spec((rows, cols), jnp.int8, one_chip),
            _spec((cols,), jnp.float32, one_chip)]
    if delta:
        args.append(_spec((rows, cols), jnp.bfloat16, one_chip))
    fn = jax.jit(lambda *a: fused_dequant(*a, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_flash_attention_compiles_for_v5e(one_chip):
    S = 2048
    args = (_spec((1, H, S, D), jnp.bfloat16, one_chip),
            _spec((1, K, S, D), jnp.bfloat16, one_chip),
            _spec((1, K, S, D), jnp.bfloat16, one_chip))
    fn = jax.jit(lambda *a: flash_attention(*a, causal=True, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("mode", ["decode", "prefill"])
def test_paged_model_step_compiles_for_v5e(one_chip, monkeypatch, mode):
    """The model's paged serving step at Qwen3-8B widths (2 of its layers:
    depth does not change tiling), bf16 weights on the engine's f32 pools.
    This process only sees the CPU, so the test steers the kernels off
    interpret mode itself."""
    from repro.configs import get_config
    from repro.configs.base import depth_cut
    from repro.kernels import ops
    from repro.models import init_params, kv_cache
    from repro.models.transformer import ModelRuntime, forward

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = depth_cut(get_config("qwen3-8b"), 2)
    rt = ModelRuntime(use_pallas=True, remat=False)
    shapes = lambda f: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), jax.eval_shape(f))
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: kv_cache.init_paged_cache(
        cfg, B, N_PAGES, PAGE_SIZE, dtype=ENGINE_POOL_DTYPE))
    bt = _spec((B, NB), jnp.int32, one_chip)
    if mode == "decode":
        fn = jax.jit(lambda p, c, t, bt: forward(
            p, cfg, rt, tokens=t, cache=c, mode="decode",
            paged={"block_tables": bt})["hidden"])
        args = (params, cache, _spec((B,), jnp.int32, one_chip), bt)
    else:
        fn = jax.jit(lambda p, c, t, bt, off: forward(
            p, cfg, rt, tokens=t, cache=c, mode="prefill",
            paged={"block_tables": bt, "q_offsets": off})["hidden"])
        args = (params, cache, _spec((B, C), jnp.int32, one_chip), bt,
                _spec((B,), jnp.int32, one_chip))
    _assert_kernel(fn.lower(*args).compile())
