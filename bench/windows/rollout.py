"""Timed entry of the rollout cells: GRPO rollout on the paged engine.

Set-up, counted in ``setup_s``:
  1. weights from the seed (``bench/weights.py``) and an ``InferenceEngine``
     with the configuration's engine settings; the pool is made at its cap,
     so it never grows;
  2. warm-up of every program shape the mix can reach (decode block-table
     widths, prefill row counts, chunk widths and table widths, first-token
     sampling, copy-on-write copies), executed on idle rows that write only
     to the garbage page;
  3. admission: groups from the stream are admitted, each member with
     ``PROGRESS`` of its response already generated (the prompt plus as many
     random tokens, the context a partial rollout brings when it migrates
     onto an instance), until the engine refuses one.  Nothing is prefilled
     yet.

Window: ``seconds`` of ``step()`` calls from there, under the rollout
instance's backpressure rule (``core/instance.RolloutInstance._admit``:
admit queued groups with ``add_group`` until ``AdmissionError``, keep the
rest queued).  The admitted contexts are prefilled by the engine's chunked
prefill inside the window.  A response that reaches its drawn length is
ended there, as if EOS had been sampled.  The queue never runs dry.

After the window: peak memory is read, the engine is freed, and a sample of
requests (the longest finished one first) is scored by the plain reference
(``bench/check.py``).
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, modelcfg, peaks, trace, weights
from bench.traffic import Stream

PROGRESS = 0.5      # share of each response generated before admission


class _Spans:
    """Engine tracer that opens a profiler annotation per span, so the trace
    can name what the host was doing in each idle gap."""

    enabled = True

    def span(self, name, lane, **_):
        return jax.profiler.TraceAnnotation(name)

    def begin(self, name, lane, **_):
        a = jax.profiler.TraceAnnotation(name)
        a.__enter__()
        return a

    def end(self, span, **_):
        span.__exit__(None, None, None)

    def event(self, *a, **k):
        pass


class CompileCount:
    """Programs traced (a new shape) and backend compiles, from JAX's own
    monitoring events; read before and after the window."""

    def __init__(self):
        self.traced = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self):
        return (self.traced, self.compiled)


def _bucket(n: int, minimum: int) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _powers(top: int) -> List[int]:
    """1, 2, 4, ... up to ``top``."""
    return [1 << i for i in range(top.bit_length()) if 1 << i <= top]


def _pages(n_tokens: int, page_size: int) -> int:
    return -(-n_tokens // page_size)


class Rollout:
    """One run of a rollout cell (one process, one chip)."""

    def __init__(self, cell: dict, seed: int, *, traced: bool = False):
        from repro.serving.engine import InferenceEngine

        self.cell = cell
        self.c = cell["config"]
        self.mix = cell["traffic"]
        self.seed = seed
        self.cfg = modelcfg.to_model_config(self.c)
        self.params = weights.make(self.c, seed, cell["root"])
        weights.check_layout(self.params, self.cfg)
        jax.block_until_ready(self.params)
        self.pool_pages = int(self.c["engine"]["pool_pages"])
        self.engine = InferenceEngine(
            self.cfg, self.params,
            **modelcfg.engine_kwargs(self.c, self.pool_pages),
            tracer=_Spans() if traced else None)
        eng = self.engine
        if eng.alloc.num_pages != self.pool_pages:
            raise RuntimeError(f"pool made at {eng.alloc.num_pages} pages, "
                               f"not at its cap {self.pool_pages}")
        jax.block_until_ready(eng.cache)
        self.stream = Stream(self.mix, seed, self.c["vocab_size"])
        self.queue: List = []
        self.req: Dict[int, dict] = {}      # rid -> bookkeeping
        self.traced_steps: List[dict] = []
        self._tracing = False

    # ------------------------------------------------------------------ #
    # warm-up
    # ------------------------------------------------------------------ #
    def warm_up(self):
        """Compile and run, once, each program shape the mix can reach.

        Rows are idle (inactive decode rows, masked prefill rows whose slot
        index is out of range): every write lands on the garbage page, and
        nothing of the engine's state changes."""
        from repro.serving import engine as em

        eng, cfg, mix = self.engine, self.cfg, self.mix
        ps, B, G = eng.page_size, eng.max_batch, int(mix["group_size"])
        hi = int(mix["prompt_len"]["hi"]) + int(mix["response_cap"]) + eng.horizon
        widths = [8]
        while widths[-1] < _pages(hi, ps):
            widths.append(widths[-1] * 2)
        tok = jnp.full((B,), em.TOKEN_SENTINEL, jnp.int32)
        for nb in widths:
            fn = em._get_decode_fn(cfg, eng.rt, nb, eng.temperature, eng.horizon)
            eng.cache, *_ = fn(eng.params, eng.cache, jnp.array(tok),
                               jnp.zeros((B, 2), jnp.uint32),
                               jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
                               jnp.zeros((B, nb), jnp.int32))
        # prefill tables: fresh prompts, up to the admitted partial contexts
        lo_pages = _pages(int(mix["prompt_len"]["lo"]), ps)
        hi_pages = _pages(int(mix["prompt_len"]["hi"])
                          + int(PROGRESS * int(mix["response_cap"])), ps)
        nbs = [w for w in widths if _bucket(lo_pages, 8) <= w
               <= _bucket(hi_pages, 8)]
        chunks = sorted({em._tile_bucket(c) for c in range(1, eng.prefill_chunk + 1)})
        # rows in one prefill step: the rest of one context, then whole
        # ones of at least the shortest prompt, within the chunk's budget
        k = min(B, 2 + (eng.prefill_chunk - 2) // int(mix["prompt_len"]["lo"]))
        for n in _powers(_bucket(k, 1)):
            for C in chunks:
                for nb in nbs:
                    fn = em._get_prefill_fn(cfg, eng.rt, n, C, nb)
                    eng.cache, _ = fn(eng.params, eng.cache,
                                      jnp.full((n,), B, jnp.int32),
                                      jnp.zeros((n, C), jnp.int32),
                                      jnp.zeros((n, C), jnp.float32),
                                      jnp.zeros((n,), jnp.int32),
                                      jnp.zeros((n, nb), jnp.int32))
        # first tokens of every member of the rows finishing in one step,
        # and the copy-on-write copies at the first decode of their groups
        for m in _powers(_bucket(min(B, k * G), 1)):
            em._get_batch_sample_fn(eng.temperature, m)(
                jnp.zeros((m, cfg.vocab_size), jnp.float32),
                jnp.zeros((m, 2), jnp.uint32), jnp.zeros((m,), jnp.int32))
            if G > 1:
                z = jnp.zeros((m,), jnp.int32)
                eng.cache = em._get_copy_fn(cfg, m)(eng.cache, z, z)
        pos = np.asarray(eng.cache["pos"]).tolist()
        for n in range(G - 1, min(B, k * (G - 1)) + 1, G - 1) if G > 1 else ():
            idx = jnp.asarray(list(range(n)), jnp.int32)      # siblings' pos
            eng.cache["pos"] = eng.cache["pos"].at[idx].set(
                jnp.asarray(pos[:n], jnp.int32))
        jax.block_until_ready(eng.cache)

    # ------------------------------------------------------------------ #
    # steady state
    # ------------------------------------------------------------------ #
    def fill(self):
        """Admit whole groups from the stream, each member with
        ``PROGRESS`` of its response already generated, until the engine
        refuses a member (that group is taken back).  Members are admitted
        one by one, since their partial responses differ."""
        from repro.serving.engine import AdmissionError

        eng = self.engine
        rng = np.random.default_rng([int(self.seed) % 2**64, 1])
        while True:
            g = next(self.stream)
            added = []
            try:
                for r in g.requests:
                    part = rng.integers(3, self.c["vocab_size"],
                                        size=int(PROGRESS * r.drawn)).tolist()
                    eng.add_request(r.rid, g.prompt + part, r.key, g.max_total,
                                    len(g.prompt))
                    added.append(r.rid)
                    self._track(r, g, part)
            except AdmissionError:
                for rid in added:
                    eng.drop_request(rid)
                    del self.req[rid]
                break
        if not self.req:
            raise RuntimeError("the engine admits no group of this mix")
        self.resident0 = len(self.req)

    def _track(self, r, g, part=()):
        self.req[r.rid] = {"drawn": r.drawn, "max_total": g.max_total,
                           "prefix": list(g.prompt) + list(part),
                           "n_prompt": len(g.prompt), "served": [], "lps": [],
                           "t_last": None, "finished": False}

    # ------------------------------------------------------------------ #
    # the loop
    # ------------------------------------------------------------------ #
    def _admit(self):
        from repro.serving.engine import AdmissionError

        while True:
            if not self.queue:
                self.queue.append(next(self.stream))
            g = self.queue[0]
            members = [(r.rid, r.key, g.max_total) for r in g.requests]
            try:
                self.engine.add_group(members, g.prompt, len(g.prompt))
            except AdmissionError:
                return
            self.queue.pop(0)
            for r in g.requests:
                self._track(r, g)

    def _step(self) -> int:
        """One ``step()``; returns the tokens it handed over, and records the
        gap of each emission (time since that request's previous emission
        in the window, over the tokens it carried)."""
        eng = self.engine
        rec = self._pre_step() if self._tracing else None
        events = eng.step()
        t = time.perf_counter()
        got: Dict[int, int] = {}
        for ev in events:
            q = self.req[ev.req_id]
            if q["finished"]:
                continue                      # past its drawn length
            q["served"].append(ev.token)
            q["lps"].append(ev.logprob)
            got[ev.req_id] = got.get(ev.req_id, 0) + 1
            n_gen = len(q["prefix"]) - q["n_prompt"] + len(q["served"])
            if n_gen >= q["drawn"] or ev.finished:
                q["finished"] = True
                self.finishes.append((len(self.step_s), ev.req_id,
                                      "drawn" if n_gen >= q["drawn"] else "engine"))
                if not ev.finished:
                    eng.drop_request(ev.req_id)
        for rid, n in got.items():
            q = self.req[rid]
            if q["t_last"] is not None:
                self.gaps.append((t - q["t_last"]) / n)
            q["t_last"] = t
        if rec is not None:
            self.traced_steps.append(rec)
        return sum(got.values())

    def _pre_step(self) -> dict:
        """What the next step will do, read from the engine's slots, waiting
        rows and allocator (traced run only)."""
        eng = self.engine
        ctx = [s.ctx_len + 1 for s in eng.slots if s is not None]
        budget = max(eng.prefill_chunk, 1)
        rows = []
        for row in eng.waiting:
            if budget <= 0:
                break
            take = min(len(row.token_ids) - row.done, budget)
            rows.append((row.done, take, row.done + take == len(row.token_ids)))
            budget -= take
        resident = eng.active_request_ids()
        committed = sum(_pages(self.req[r]["max_total"], eng.page_size)
                        for r in resident if r in self.req)
        used = eng.alloc.num_pages - 1 - eng.alloc.n_free
        return {"decode_ctx": ctx, "prefill_rows": rows, "pages_used": used,
                "pages_committed": committed}

    def window(self, seconds: float, trace_dir=None,
               trace_start=0.3, trace_seconds=3.0):
        """Step for ``seconds``; with ``trace_dir``, profile a stretch of
        ``trace_seconds`` that starts ``trace_start`` of the way in."""
        self.gaps: List[float] = []
        self.step_s: List[float] = []        # each step's seconds, host clock
        self.finishes: List[tuple] = []      # (step, request, what ended it)
        counts0 = self.counts.snapshot()
        tokens = 0
        t0 = time.perf_counter()
        t_end, t_trace, t_stop = t0 + seconds, t0 + trace_start * seconds, None
        span = None
        while time.perf_counter() < t_end:
            if trace_dir and t_stop is None and time.perf_counter() >= t_trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
                span.__enter__()
                self._tracing = True
                t_stop = time.perf_counter() + trace_seconds
            if self._tracing:
                with jax.profiler.TraceAnnotation("bench.admit"):
                    self._admit()
            else:
                self._admit()
            ts = time.perf_counter()
            tokens += self._step()
            self.step_s.append(time.perf_counter() - ts)
            if self._tracing and time.perf_counter() >= t_stop:
                self._stop_trace(span)
        self.window_s = time.perf_counter() - t0
        if self._tracing:
            self._stop_trace(span)
        self.tokens = tokens
        self.window_counts = tuple(
            b - a for a, b in zip(counts0, self.counts.snapshot()))

    def _stop_trace(self, span):
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False

    # ------------------------------------------------------------------ #
    def run(self, seconds: float, t_process: float, *, trace_run: bool = False,
            control: bool = False) -> dict:
        self.counts = CompileCount()
        t = time.perf_counter()
        self.warm_up()
        t_warm = time.perf_counter()
        self.fill()
        t_fill = time.perf_counter()
        setup_s = t_fill - t_process
        self.setup_parts = {"to weights and engine": t - t_process,
                            "warm-up": t_warm - t, "admission": t_fill - t_warm}
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace_run else None
        try:
            self.window(seconds, trace_dir=trace_dir)
            devs = jax.local_devices()[: self.cell["chips"]]
            stats = [d.memory_stats() or {} for d in devs]
            mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
            self.bytes_limit = min(s.get("bytes_limit", 0) for s in stats)
            t = time.perf_counter()
            record = self._record(trace_dir) if trace_run else None
            self.record_s = time.perf_counter() - t
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        served = [q for q in self.req.values() if q["served"]]
        sample = self._sample()
        del self.engine, self.params
        gc.collect()
        t = time.perf_counter()
        res = check.compare_served(self.c, self.seed, sample,
                                   self.cell["limits"], control=control,
                                   root=self.cell["root"])
        reference_s = time.perf_counter() - t
        gaps_ms = sorted(1e3 * g for g in self.gaps)
        return {
            "setup_s": setup_s,
            "window_s": self.window_s,
            "tokens": self.tokens,
            "rollout_tokens_per_s": self.tokens / self.window_s,
            "token_gap_p95_ms": (float(np.quantile(gaps_ms, 0.95,
                                                   method="higher"))
                                 if gaps_ms else None),
            "n_gaps": len(gaps_ms),
            "compiles_in_window": self.window_counts,
            "memory_peak_bytes": int(mem_peak),
            "attempted": len(served),
            "finished": sum(q["finished"] for q in served),
            "check": res,
            "reference_s": reference_s,
            "record": record,
        }

    def _sample(self) -> List[dict]:
        """Requests to score: the finished ones, the longest first and the
        rest in an order drawn from the seed, then the in-flight ones with
        the most served tokens, until the mix's ``check.requests`` requests
        and the limits' ``tokens_compared.min`` served tokens are in."""
        n_req = int(self.mix["check"]["requests"])
        n_tok = int(self.cell["limits"]["tokens_compared"]["min"])
        rng = np.random.default_rng([int(self.seed) % 2**64, 2])
        served = [q for q in self.req.values() if q["served"]]
        fin = sorted((q for q in served if q["finished"]),
                     key=lambda q: -len(q["served"]))
        if fin:
            fin = fin[:1] + [fin[1:][i] for i in rng.permutation(len(fin) - 1)]
        rest = sorted((q for q in served if not q["finished"]),
                      key=lambda q: -len(q["served"]))
        out, n = [], 0
        for q in fin + rest:
            if len(out) >= n_req and n >= n_tok:
                break
            out.append({"tokens": q["prefix"] + q["served"],
                        "n_before": len(q["prefix"]), "lps": list(q["lps"])})
            n += len(q["served"])
        return out

    # ------------------------------------------------------------------ #
    def _record(self, trace_dir) -> dict:
        pd = trace.load_planes(trace_dir)
        red = trace.reduce(pd) if pd is not None else {}
        eng = self.engine
        dev = jax.devices()[0]
        dt = next(x.dtype for p, x in jax.tree_util.tree_leaves_with_path(eng.cache)
                  if jax.tree_util.keystr(p).endswith("['k_pages']"))
        return {
            "config": self.c,
            "trace": red,
            "steps": self.traced_steps,
            "pool_itemsize": jnp.dtype(dt).itemsize,
            "act_itemsize": jnp.dtype(self.c["torch_dtype"]).itemsize,
            "peak": peaks.for_kind(dev.device_kind),
            "chips": self.cell["chips"],
        }


def run_cell(cell: dict, seed: int, seconds: float, t_process: float, *,
             trace: bool = False, control: bool = False) -> dict:
    """The window kind's entry, as ``bench/run.py`` calls it."""
    r = Rollout(cell, seed, traced=trace)
    out = r.run(seconds, t_process, trace_run=trace, control=control)
    out["notes"] = {
        "programs traced / compiled inside the window": out["compiles_in_window"],
        "tokens in window": f"{out['tokens']} over {out['window_s']:.3f} s, "
                            f"{out['n_gaps']} gaps",
        "requests served / finished": f"{out['attempted']} / {out['finished']}",
        "memory_peak_bytes": out["memory_peak_bytes"],
        "bytes_limit": r.bytes_limit,
        "set-up seconds": {k: round(v, 3) for k, v in r.setup_parts.items()},
        "admitted at window start": r.resident0,
        "steps in window / slowest ms": f"{len(r.step_s)} / "
                                        f"{1e3 * max(r.step_s, default=0):.1f}",
        "finishes in window (step, request, ended by)": r.finishes,
        "reference seconds": round(out["reference_s"], 3),
        "trace read seconds": round(r.record_s, 3),
    }
    return out
