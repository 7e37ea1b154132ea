"""Training launcher: GRPO on any assigned architecture, single-host or on
a device mesh, with checkpoint/restart supervision.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
      --steps 20 --ckpt-dir /tmp/rl_ckpt

On a TPU host, drop --reduced, set --data/--model mesh axes and, where the
model does not fit, cut depth with --layers (e.g. ``--arch qwen3-8b
--layers 8 --data 2 --model 2`` on four v5e chips).  Params and AdamW
state are created sharded (``init_sharded_state``), so no device ever
holds the whole state.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.configs.base import depth_cut
from repro.data import tokenizer as tok
from repro.distributed import sharding as shd
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import abstract_state
from repro.models import init_params
from repro.models.transformer import ModelRuntime
from repro.rl import grpo


def synthetic_batch(cfg, key, B, S):
    ks = jax.random.split(key, 3)
    batch = {
        "response_mask": jnp.ones((B, S)).at[:, : S // 4].set(0.0),
        "advantages": grpo.group_advantages(
            jax.random.uniform(ks[1], (B,)), 2 if B % 2 == 0 else 1),
        "behavior_logprobs": jnp.zeros((B, S)) - 2.0,
    }
    if cfg.input_mode == "embeds":
        batch["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model),
                                            jnp.bfloat16)
        if cfg.is_decoder:
            batch["tokens"] = jax.random.randint(ks[2], (B, S), 3,
                                                 cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(ks[0], (B, S), 3,
                                             cfg.vocab_size)
    if not cfg.is_decoder:
        batch = {"embeds": batch["embeds"],
                 "labels": jax.random.randint(ks[2], (B, S), 0,
                                              cfg.vocab_size),
                 "mask": jnp.ones((B, S))}
    return batch


def state_shardings(cfg, mesh, recipe: str = "fsdp_tp"):
    """NamedShardings of the train state ``{"params", "opt"}`` on ``mesh``
    (AdamW m/v/master follow their params)."""
    shapes = abstract_state(cfg)
    pspecs = shd.param_specs(cfg, shapes["params"], recipe, mesh=mesh)
    return shd.to_named({"params": pspecs,
                         "opt": shd.opt_specs(cfg, shapes["opt"], pspecs)},
                        mesh)


def init_sharded_state(cfg, key, mesh, recipe: str = "fsdp_tp"):
    """Params + AdamW state created in place on ``mesh``: the init is one
    jitted program whose outputs are already sharded, so each device only
    ever writes its own shards.  Returns ``(state, shardings)``."""
    sharding = state_shardings(cfg, mesh, recipe)
    init = jax.jit(lambda k: grpo.init_train_state(init_params(cfg, k)),
                   out_shardings=sharding)
    return init(key), sharding


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers (published widths)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--recipe", default="fsdp_tp")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=max(tok.VOCAB_SIZE, 64))
    if args.layers is not None:
        cfg = depth_cut(cfg, args.layers)
    mesh = make_local_mesh(args.data, args.model)
    rt = shd.make_runtime(cfg, mesh, args.recipe, remat=True,
                          q_block=min(args.seq, 512))

    key = jax.random.PRNGKey(0)
    state, sharding = init_sharded_state(cfg, key, mesh, args.recipe)

    start = 0
    if args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            state, _ = ckpt.restore(ckpt.step_path(args.ckpt_dir, last),
                                    state, sharding)
            start = last
            print(f"[restart] resumed from step {last}")

    loss_kind = "grpo" if cfg.is_decoder else "supervised"
    step_fn = jax.jit(grpo.make_train_step(cfg, rt, lr=args.lr,
                                           loss_kind=loss_kind),
                      out_shardings=(sharding, None), donate_argnums=(0,))
    saver = ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None

    for i in range(start, args.steps):
        batch = synthetic_batch(cfg, jax.random.fold_in(key, i),
                                args.batch, args.seq)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), "training diverged"
        print(f"step {i:4d} loss={loss:.4f} "
              f"grad_norm={float(metrics['grad_norm']):.3f} "
              f"({time.time() - t0:.2f}s)", flush=True)
        if saver and (i + 1) % args.ckpt_every == 0:
            saver.save(state, step=i + 1, block=False)
    if saver:
        saver.wait()
    print("done")


if __name__ == "__main__":
    main()
