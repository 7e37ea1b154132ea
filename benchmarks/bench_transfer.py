"""[Transfer plane] Cold-provision time of one rollout instance vs chunk
count x compression x peer count (the chunk-level pull scheduler on the
event clock, qwen3-14b-sized weights), plus the fused dequant/delta-
accumulate kernel's oracle error and TPU roofline bound.

Cold-provision time is the paper's "how fast does a new instance become
productive" axis (Fig 14/17): chunking adds no serial overhead, peers
multiply sender bandwidth until the receiver NIC saturates, and the int8 /
delta-int8 codecs cut wire bytes 2x / 4x.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.events import EventLoop
from repro.core.perfmodel import SPOT_INSTANCE, model_perf_from_cfg
from repro.core.weight_transfer import TransferAgent
from repro.kernels import ref
from repro.kernels.dequant import fused_dequant
from repro.kernels.ops import interpret_mode
from repro.launch.hlo_analysis import HBM_BW
from repro.transfer.chunkstore import synthetic_manifest
from repro.transfer.puller import ChunkPull
from benchmarks.common import emit

OUT = Path("experiments/bench")


def cold_provision(weight_bytes, *, n_chunks, codec, peers, receivers=16,
                   fanout=4, agent_gbps=400.0,
                   receiver_gbps=SPOT_INSTANCE.dcn_gbps):
    """Provision ``receivers`` cold instances at once from ``peers``
    transfer agents; returns mean completion time.  With few peers the
    sender NIC is the contended resource (per-chunk shares re-divide);
    with enough peers each receiver saturates its own 50 gbps NIC."""
    loop = EventLoop()
    agents = [TransferAgent(i, agent_gbps) for i in range(peers)]
    m = synthetic_manifest(1, weight_bytes, n_chunks, codec=codec,
                           base_version=0 if codec == "delta-int8" else None)
    t = []
    for _ in range(receivers):
        ChunkPull(loop, agents, m, receiver_gbps=receiver_gbps, cache={},
                  fanout=fanout,
                  on_complete=lambda p: t.append(loop.now)).start()
    loop.run()
    return float(np.mean(t))


def main(quick: bool = False):
    OUT.mkdir(parents=True, exist_ok=True)
    perf = model_perf_from_cfg(get_config("qwen3-14b"))
    wb = perf.weight_bytes

    chunk_counts = [64] if quick else [16, 64, 256, 1024]
    peer_counts = [1, 4] if quick else [1, 2, 4, 8]
    out = {}
    for codec in ["none", "int8", "delta-int8"]:
        for n_chunks in chunk_counts:
            for peers in peer_counts:
                t = cold_provision(wb, n_chunks=n_chunks, codec=codec,
                                   peers=peers)
                key = f"{codec}/c{n_chunks}/p{peers}"
                out[key] = t
                emit(f"transfer/cold_provision/{key}", t, wb / max(t, 1e-9))

    # fused dequant/delta-accumulate kernel: oracle error + roofline bound
    R, C = (512, 512) if quick else (4096, 1024)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-127, 128, (R, C)), jnp.int8)
    scale = jnp.asarray(rng.uniform(1e-4, 1e-2, (C,)), jnp.float32)
    base = jnp.asarray(rng.randn(R, C), jnp.float32)
    o = fused_dequant(q, scale, base, interpret=interpret_mode())
    r = ref.dequant_ref(q, scale, base)
    err = float(jnp.abs(o - r).max())
    # fused pass: read int8 q + f32 base, write f32 out (scale negligible)
    byts = R * C * (1 + 4 + 4) + 4 * C
    # unfused dequant-then-add would also round-trip the f32 delta: +2 R*C*4
    byts_unfused = byts + 2 * R * C * 4
    emit("transfer/dequant_kernel/err", err, byts, byts / HBM_BW * 1e6)
    emit("transfer/dequant_kernel/fused_traffic_ratio",
         byts / byts_unfused)
    out["dequant"] = dict(err=err, bytes=byts,
                          roofline_us=byts / HBM_BW * 1e6,
                          fused_traffic_ratio=byts / byts_unfused)
    (OUT / "transfer.json").write_text(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
