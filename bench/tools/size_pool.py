"""Size a configuration's KV pool from the compiler's memory analysis.

  JAX_PLATFORMS=cpu python3 bench/tools/size_pool.py bench/configs/<c>.json

Compiles the engine's decode and prefill programs ahead of time for one
chip of a described v5e at two pool sizes, fits the programs' temporary
bytes as a line in the pool's bytes, and prints the largest pool for
which parameters + pool + temp stay under the usable HBM less a margin.
The cache is the one the engine makes with the arguments the rollout window
gives it (``modelcfg.engine_kwargs``).  Needs no chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
USABLE_GIB = 15.75        # HBM a v5e program may use (16 GiB less the runtime's)
MARGIN_GIB = 0.5


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import modelcfg, weights
    from repro.kernels import ops
    from repro.serving import engine as em

    jax.config.update("jax_enable_compilation_cache", False)
    ops.interpret_mode = lambda: False          # compile the kernels for the chip
    c = modelcfg.load(args.config)
    cfg = modelcfg.to_model_config(c)
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    S = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)
    params = jax.tree.map(S, jax.eval_shape(lambda: weights.make(c, 0)))
    p_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    rt = dataclasses.replace(em.CPU_RT, use_pallas=True)
    temps = []
    for P in (1025, 4097):
        kw = modelcfg.engine_kwargs(c, P)
        B = kw["max_batch"]
        cache = jax.tree.map(S, jax.eval_shape(
            lambda: em.InferenceEngine(cfg, params, **kw).cache))
        c_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        dec = em._get_decode_fn(cfg, rt, 1024, kw["temperature"], 1).lower(
            params, cache, S(jnp.zeros((B,), jnp.int32)),
            S(jnp.zeros((B, 2), jnp.uint32)), S(jnp.zeros((B,), bool)),
            S(jnp.zeros((B,), jnp.int32)), S(jnp.zeros((B, 1024), jnp.int32)))
        pre = em._get_prefill_fn(cfg, rt, 2, 256, 1024).lower(
            params, cache, S(jnp.zeros((2,), jnp.int32)),
            S(jnp.zeros((2, 256), jnp.int32)), S(jnp.zeros((2, 256), jnp.float32)),
            S(jnp.zeros((2,), jnp.int32)), S(jnp.zeros((2, 1024), jnp.int32)))
        t = max(f.compile().memory_analysis().temp_size_in_bytes for f in (dec, pre))
        temps.append((c_bytes, t))
        print(f"pool {P} pages: {c_bytes / 2**30:.3f} GiB, program temp "
              f"{t / 2**30:.3f} GiB", flush=True)
    (x0, t0), (x1, t1) = temps
    slope = (t1 - t0) / (x1 - x0)
    icpt = t0 - slope * x0
    page = x1 / 4097
    free = (USABLE_GIB - MARGIN_GIB) * 2**30 - p_bytes - icpt
    pages = int(free / (page * (1 + slope)))
    print(f"params {p_bytes / 2**30:.3f} GiB; temp = {slope:.3f} x pool "
          f"{icpt / 2**30:+.3f} GiB; page {page / 2**10:.0f} KiB; "
          f"pool_pages {pages}")


if __name__ == "__main__":
    main()
