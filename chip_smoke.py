"""Chip smoke test: the rollout engine and the GRPO trainer on a TPU, at
Qwen3-8B's published widths cut to its first 8 of 32 layers (random
weights made from ``SEED``).

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # four chips: sharded train steps only

One chip, in one process:
  1. an ``InferenceEngine`` on the ragged paged Pallas kernels admits two
     GRPO groups of G=8 with 1-2k-token prompts (chunked prefill, shared
     prompt pages) and decodes 64 new tokens each, 8 per dispatch;
  2. mid-generation it swaps in weights assembled from an ``int8`` chunk
     manifest (dequantized by the fused Pallas kernel), and exports one
     partial request over a ``none``-codec KV manifest into a second engine,
     which must resume it with zero prefill and the source's exact tokens;
  3. the compiled decode and prefill programs must contain the kernels
     (``tpu_custom_call``);
  4. first-token logprobs of the Pallas engine must match a dense
     (``use_pallas=False``) engine on the same prompts;
  5. one RL step of ``RealRLHarness`` at its test size.

``--four-chips`` runs only ``grpo.make_train_step`` on a 2x2 ``fsdp_tp``
mesh with remat, against a forward-only ``grpo_loss`` on one chip.

Any failed check exits non-zero.  The last line of standard output is one
JSON object naming the device, printed only after every phase passed.
Without a TPU, or without the repo's ``src/`` beside it, it refuses to run.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH, LAYERS, SEED = "qwen3-8b", 8, 0

# engine phase: two GRPO groups; prompt lengths are page-unaligned (the
# boundary page is copied on write) and chunk as 512, 512, 504+8, 512, 512
G, MAX_NEW, HORIZON, PREFILL_CHUNK = 8, 64, 8, 512
PROMPT_LENS = (1528, 1032)
SWAP_AFTER_STEP, EXPORT_AFTER_STEP = 5, 7     # both prompts done by step 4
# Pallas vs dense engine, bf16 weights and activations: the dense oracle
# rounds the pooled prefix to bf16, the kernel reads it in f32
LOGPROB_TOL = 0.1          # nats, on first tokens both engines sampled
MIN_SAME_FIRST_TOKEN = 12  # of 16: near-flat random-weight logits can flip

# four-chip phase
TRAIN_B, TRAIN_S, TRAIN_STEPS = 8, 512, 3
LOSS_TOL = 0.02            # |sharded step-0 loss - one-chip loss|, bf16
RATIO_TOL = 0.02           # |step-0 importance ratio mean - 1|, on-policy


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


class CompileClock:
    """Seconds the backend spent compiling (persistent-cache hits add 0)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.n += 1


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #
def int8_weight_swap(params, version: int):
    """Publish ``params`` and assemble them back from an int8 manifest, as
    a rollout instance installs a pulled version.  Checks every leaf
    against the quantizer's bound."""
    import jax
    import numpy as np

    from repro.kernels.ops import interpret_mode
    from repro.transfer.chunkstore import ChunkStore

    store = ChunkStore()
    store.publish(version, params)
    m = store.manifest(version, codec="int8")
    chunks = {d: store.fetch(d) for d in m.digests()}
    new = store.assemble(m, chunks, like=params,
                         use_pallas=not interpret_mode())
    worst = 0.0
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        amax = float(np.abs(a).max())
        # int8 step <= amax/127 (half of it per element), plus the cast
        # of the f32 dequant back to the leaf's bf16
        bound = amax / 254 + amax * 2.0 ** -8 + 1e-12
        err = float(np.abs(a - b).max())
        check(np.isfinite(b).all(), "int8 swap produced non-finite weights")
        check(err <= bound, f"int8 swap error {err} > bound {bound}")
        worst = max(worst, err / bound)
    return new, m, worst


def run_engine_phase(cfg, params, seed: int):
    import numpy as np

    from repro.launch.serve import serve_requests
    from repro.rl.sampler import request_key
    from repro.serving.engine import InferenceEngine
    from repro.transfer.chunkstore import assemble_kv_state, build_kv_manifest

    kw = dict(max_batch=2 * G, slab_len=256, page_size=16,
              prefill_chunk=PREFILL_CHUNK, horizon=HORIZON, temperature=1.0)
    rng = np.random.RandomState(seed)
    groups = []
    for g, L in enumerate(PROMPT_LENS):
        ids = rng.randint(3, cfg.vocab_size, size=L).tolist()  # no PAD/BOS/EOS
        members = [(g * G + j, request_key(seed, g * G + j), L + MAX_NEW)
                   for j in range(G)]
        groups.append((members, ids, L))

    src = InferenceEngine(cfg, params, use_pallas=True, **kw)
    mig = {}

    def on_step(i, events):
        version = int(i > SWAP_AFTER_STEP)
        check(all(ev.weight_version == version for ev in events),
              f"step {i} emitted tokens not from weight version {version}")
        if i == SWAP_AFTER_STEP:
            check(not src.waiting, "prefill still running at the swap")
            t = time.perf_counter()
            new, m, worst = int8_weight_swap(src.params, version=1)
            src.swap_weights(new, version=1)
            log(f"int8 weight swap: {m.n_chunks} chunks, "
                f"{m.total_bytes / 2**30:.2f} GiB encoded, assembled with "
                f"fused_dequant and installed mid-generation in "
                f"{time.perf_counter() - t:.1f}s (worst leaf error "
                f"{worst:.2f} of its bound)")
        if i == EXPORT_AFTER_STEP:
            rid = next(r for r in src.exportable_request_ids() if r >= G)
            state = src.export_request_state([rid])
            m, blobs, meta = build_kv_manifest(0, state, codec="none")
            dst = InferenceEngine(cfg, src.params,
                                  weight_version=src.weight_version,
                                  use_pallas=True, **kw)
            dst.import_request_state(assemble_kv_state(m, blobs, meta))
            # the source keeps decoding it too: the copies must agree
            req = state["requests"][0]
            mig.update(rid=rid, dst=dst, ctx=req["ctx_len"],
                       n_pages=state["n_pages"],
                       cut=len(req["tokens"]) - req["n_prompt"])

    t = time.perf_counter()
    out = serve_requests(src, groups, on_step=on_step)
    check("dst" in mig, "no request was exported")
    dst, rid = mig["dst"], mig["rid"]
    out_dst = serve_requests(dst, [])
    wall = time.perf_counter() - t

    n_tok = sum(len(v) for v in out.values()) + len(out_dst.get(rid, []))
    for r, evs in out.items():
        check(evs and evs[-1].finished, f"request {r} did not finish")
        check(all(math.isfinite(ev.logprob) for ev in evs),
              f"request {r} has non-finite logprobs")
        check(len(evs) == MAX_NEW or evs[-1].token == 2,
              f"request {r}: {len(evs)} tokens, expected {MAX_NEW}")
    log(f"engine: 2 GRPO groups x G={G}, prompts {list(PROMPT_LENS)} tokens "
        f"in chunks of {PREFILL_CHUNK} ({src.n_prefills} prefills, "
        f"{src.n_shared_prompt_tokens} prompt tokens shared), horizon "
        f"{HORIZON}, {src.n_decode_dispatches} decode dispatches")

    # the migrated request resumed without prefill, bit-identical to the
    # source engine's own continuation
    tail_src = [(ev.token, ev.logprob) for ev in out[rid][mig["cut"]:]]
    tail_dst = [(ev.token, ev.logprob) for ev in out_dst[rid]]
    check(dst.n_prefill_tokens == 0 and dst.n_prefills == 0,
          f"import prefilled {dst.n_prefill_tokens} tokens")
    check(dst.n_kv_import_tokens == mig["ctx"] > 0, "nothing was imported")
    first_diff = next((i for i, (a, b) in enumerate(zip(tail_dst, tail_src))
                       if a != b), None)
    check(tail_dst == tail_src,
          f"imported request diverged: {len(tail_dst)} vs {len(tail_src)} "
          f"tokens, first difference at {first_diff}")
    log(f"kv migration: request {rid} exported after {mig['cut']} tokens "
        f"({mig['ctx']} context tokens, {mig['n_pages']} pages, codec none) "
        f"and imported with {dst.n_prefill_tokens} prefill tokens; its "
        f"{len(tail_dst)} resumed tokens match the source bit for bit")
    return src, out, n_tok, wall, groups, kw


def check_kernels_compiled(cfg, engine):
    """Every compiled decode/prefill closure of ``engine`` holds the
    Pallas kernels as TPU custom calls."""
    import jax.numpy as jnp

    from repro.serving import engine as engine_mod

    seen = {"decode": 0, "prefill": 0}
    for key, fn in list(engine_mod._JIT_CACHE.items()):
        kind = key[0]
        if kind not in seen or key[1] != cfg.name or not key[-2]:
            continue
        if kind == "decode":
            B, nb = engine.max_batch, key[-1]
            args = (jnp.zeros((B,), jnp.int32), jnp.zeros((B, 2), jnp.uint32),
                    jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
                    jnp.zeros((B, nb), jnp.int32))
        else:
            n, C, nb = key[3], key[4], key[-1]
            args = (jnp.zeros((n,), jnp.int32), jnp.zeros((n, C), jnp.int32),
                    jnp.zeros((n, C), jnp.float32), jnp.zeros((n,), jnp.int32),
                    jnp.zeros((n, nb), jnp.int32))
        text = fn.lower(engine.params, engine.cache, *args).compile().as_text()
        check("tpu_custom_call" in text,
              f"compiled {kind} program {key} has no tpu_custom_call")
        seen[kind] += 1
    check(seen["decode"] and seen["prefill"], f"programs checked: {seen}")
    log(f"tpu_custom_call present in the compiled decode ({seen['decode']}) "
        f"and prefill ({seen['prefill']}) programs")


def run_parity_phase(cfg, params, out_pallas, groups, kw):
    from repro.launch.serve import serve_requests
    from repro.serving.engine import InferenceEngine

    dense = InferenceEngine(cfg, params, use_pallas=False, **kw)

    def first_only(_i, events):          # no decode: first tokens suffice
        for ev in events:
            if not ev.finished:
                dense.drop_request(ev.req_id)

    out_dense = serve_requests(dense, groups, on_step=first_only)
    diffs = []
    for rid, evs in out_pallas.items():
        a, b = evs[0], out_dense[rid][0]
        if a.token == b.token:
            diffs.append(abs(a.logprob - b.logprob))
    check(len(diffs) >= MIN_SAME_FIRST_TOKEN,
          f"only {len(diffs)} of {len(out_pallas)} first tokens agree")
    worst = max(diffs)
    check(worst <= LOGPROB_TOL,
          f"first-token logprob differs by {worst} > {LOGPROB_TOL}")
    log(f"pallas vs dense engine: {len(diffs)}/{len(out_pallas)} first tokens "
        f"agree; max |logprob diff| {worst:.3e} nats, mean "
        f"{sum(diffs) / len(diffs):.3e} (tolerance {LOGPROB_TOL})")


def run_rl_step(seed: int):
    import jax
    import numpy as np

    from repro.core.hybrid_runtime import RunnerConfig
    from repro.core.spot_trace import TraceEvent
    from repro.rl.harness import RealRLHarness, tiny_math_config

    t = time.perf_counter()
    rc = RunnerConfig(mode="rlboost", n_prompts=2, group_size=2, m_b=2,
                      seed=seed, t_seed_init=5.0)
    h = RealRLHarness(tiny_math_config(), rc, max_new=6)
    h.runner.load_trace([TraceEvent(0.0, +2)])
    metrics, rewards = h.run(1)
    check(len(metrics) == 1 and len(rewards) == 1
          and math.isfinite(rewards[0]), f"RL step: rewards {rewards}")
    check(all(np.isfinite(np.asarray(x)).all()
              for x in jax.tree.leaves(h.params)),
          "RL step: params not finite")
    log(f"RealRLHarness: 1 RL step completed in "
        f"{time.perf_counter() - t:.1f}s, mean reward {rewards[0]:.3f}")


def one_chip(seed: int):
    import jax

    from repro.configs import get_config
    from repro.configs.base import depth_cut
    from repro.models import init_params

    full = get_config(ARCH)
    cfg = depth_cut(full, LAYERS)
    log(f"cut: {ARCH} at published widths (d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, head_dim {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}), first {cfg.n_layers} of "
        f"{full.n_layers} layers kept, {cfg.param_count() / 1e9:.2f}B params "
        f"in {cfg.dtype}, random weights (seed {seed}); shallow depth "
        f"inflates the host's share of every step")
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    engine, out, n_tok, wall, groups, kw = run_engine_phase(cfg, params, seed)
    check_kernels_compiled(cfg, engine)
    log(f"tokens generated: {n_tok} in {wall:.1f}s wall (compiles and the "
        f"weight swap included)")
    del engine
    run_parity_phase(cfg, params, out, groups, kw)
    run_rl_step(seed)


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #
def four_chips(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import depth_cut
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import init_sharded_state, synthetic_batch
    from repro.models import init_params
    from repro.rl import grpo

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    full = get_config(ARCH)
    cfg = depth_cut(full, LAYERS)
    log(f"cut: {ARCH} at published widths, first {cfg.n_layers} of "
        f"{full.n_layers} layers, {cfg.param_count() / 1e9:.2f}B params; "
        f"params + grads + AdamW (f32 m, v, master) sharded fsdp_tp over a "
        f"2x2 mesh, remat on; batch {TRAIN_B}x{TRAIN_S}")
    key = jax.random.PRNGKey(seed)
    sq = jax.jit(lambda t: [jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree.leaves(t)])

    # reference: forward-only grpo_loss on one chip.  The batch is on-policy
    # (behavior logprobs = these weights' own), so the sharded step-0
    # importance ratio must come out at 1
    t = time.perf_counter()
    rt1 = shd.make_runtime(cfg, None, remat=False, q_block=TRAIN_S)
    params1 = jax.jit(init_params, static_argnums=0)(cfg, key)
    batch = synthetic_batch(cfg, jax.random.fold_in(key, 1), TRAIN_B, TRAIN_S)
    batch["behavior_logprobs"] = jax.jit(
        lambda p, tk: grpo.policy_logprobs(p, cfg, rt1, tk)[0])(
            params1, batch["tokens"])
    loss1, _ = jax.jit(lambda p, b: grpo.grpo_loss(p, cfg, rt1, b))(
        params1, batch)
    loss1 = float(loss1)
    fp1 = np.asarray(sq(params1))
    del params1
    log(f"one-chip forward grpo_loss: {loss1:.6f} "
        f"({time.perf_counter() - t:.1f}s incl. compile)")

    mesh = make_local_mesh(2, 2)
    rt = shd.make_runtime(cfg, mesh, "fsdp_tp", remat=True, q_block=TRAIN_S)
    state, sharding = init_sharded_state(cfg, key, mesh)
    fp4 = np.asarray(sq(state["params"]))
    check(np.allclose(fp4, fp1, rtol=1e-3),
          "sharded init does not reproduce the one-chip params")
    batch = jax.device_put(batch, shd.to_named(
        shd.train_batch_specs(mesh, "fsdp_tp", batch), mesh))
    step = jax.jit(grpo.make_train_step(cfg, rt, lr=1e-5),
                   out_shardings=(sharding, None), donate_argnums=(0,))
    losses = []
    for i in range(TRAIN_STEPS):
        t = time.perf_counter()
        state, m = step(state, batch)
        m = {k: float(v) for k, v in m.items()}
        log(f"step {i}: loss {m['loss']:.6f} grad_norm {m['grad_norm']:.4f} "
            f"ratio_mean {m['ratio_mean']:.6f} "
            f"({time.perf_counter() - t:.1f}s wall"
            f"{', compile included' if i == 0 else ''})")
        check(math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"]),
              f"step {i}: non-finite loss or grad norm")
        losses.append(m)
    d_loss = abs(losses[0]["loss"] - loss1)
    d_ratio = abs(losses[0]["ratio_mean"] - 1.0)
    check(d_loss <= LOSS_TOL, f"step-0 loss differs by {d_loss} > {LOSS_TOL}")
    check(d_ratio <= RATIO_TOL,
          f"step-0 ratio mean off by {d_ratio} > {RATIO_TOL}")
    mem = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in mem) / 2**30
    log(f"four chips: step-0 loss parity {d_loss:.3e} (tolerance {LOSS_TOL}), "
        f"|ratio_mean - 1| {d_ratio:.3e} (tolerance {RATIO_TOL}), "
        f"peak device memory {peak:.2f} GiB")


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    clock = CompileClock()
    log(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(SEED)
        else:
            one_chip(SEED)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"compile: {clock.seconds:.1f}s over {clock.n} backend compiles; "
        f"wall: {time.perf_counter() - t0:.1f}s")
    log(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
