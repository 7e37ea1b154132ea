"""Serving launcher: batched generation on any decoder architecture.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
      --prompts "12+34=" "7*8=" --max-new 16

``--reduced`` (the default) runs the arch's tiny CPU preset; on a chip,
``--no-reduced --layers N`` runs it at its published widths cut to its
first N layers (e.g. ``--arch qwen3-8b --no-reduced --layers 8`` fits one
TPU v5e).  Weights are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.configs import get_config
from repro.configs.base import depth_cut
from repro.data import tokenizer as tok
from repro.launch.compile_cache import setup_compile_cache
from repro.models import init_params
from repro.rl.sampler import request_key
from repro.serving.engine import InferenceEngine, StepEvent

# one add_group admission: ([(req_id, key, max_total)], token_ids, n_prompt)
Group = Tuple[List[Tuple[int, object, int]], List[int], int]


def serve_requests(engine: InferenceEngine, groups: Sequence[Group], *,
                   on_step: Optional[Callable[[int, List[StepEvent]], None]]
                   = None) -> Dict[int, List[StepEvent]]:
    """Admit ``groups`` (``InferenceEngine.add_group`` arguments) and step
    ``engine`` until it holds no request.  ``on_step(i, events)`` runs
    after step ``i`` — at a horizon boundary, where ``swap_weights`` and
    KV export/drop are legal.  Returns every request's events in order
    (a request dropped by ``on_step`` keeps the events it had; requests
    imported into ``engine`` beforehand are served too)."""
    out: Dict[int, List[StepEvent]] = {}
    for members, ids, n_prompt in groups:
        engine.add_group(members, ids, n_prompt)
        out.update((m[0], []) for m in members)
    i = 0
    # prompts batch-prefill (in chunks, for long ones) inside the first
    # steps; first tokens stream out together with later decode rounds
    while engine.active_request_ids():
        events = engine.step()
        for ev in events:
            out.setdefault(ev.req_id, []).append(ev)
        if on_step is not None:
            on_step(i, events)
        i += 1
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True, help="tiny CPU preset of the arch")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers (published widths)")
    ap.add_argument("--prompts", nargs="+", default=["12+34=", "7*8="])
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--horizon", type=int, default=8,
                    help="tokens per fused decode dispatch (bit-exact vs 1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=tok.VOCAB_SIZE)
    if args.layers is not None:
        cfg = depth_cut(cfg, args.layers)
    if not cfg.is_decoder:
        raise SystemExit(f"{args.arch} is encoder-only (no decode step)")
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    max_len = max(len(tok.encode(p)) for p in args.prompts) + args.max_new
    engine = InferenceEngine(cfg, params, max_batch=len(args.prompts),
                             slab_len=max(2 * max_len, 64),
                             temperature=args.temperature,
                             horizon=args.horizon)

    groups = []
    for i, p in enumerate(args.prompts):
        ids = tok.encode(p)
        groups.append(([(i, request_key(args.seed, i),
                         len(ids) + args.max_new)], ids, len(ids)))
    t0 = time.time()
    outs = serve_requests(engine, groups)
    n_tok = sum(len(v) for v in outs.values())
    for i, p in enumerate(args.prompts):
        toks = [ev.token for ev in outs[i]]
        print(f"{p!r} -> {tok.decode(tok.strip_special(toks))!r}")
    print(f"{n_tok} tokens in {time.time() - t0:.2f}s "
          f"(continuous batching, {len(args.prompts)} slots)")


if __name__ == "__main__":
    main()
