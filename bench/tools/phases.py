"""The engine's own spans and step counters on a traced run of a rollout cell.

  python3 bench/tools/phases.py --workload <cell> --seeds 1,2 --seconds 51

``engine_record`` reduces the program's tracer's spans to plain data, kept
as ``record["engine"]``; ``step_host_ms``, ``prefill_wait_ms`` and
``engine_steps`` read a record that holds it, as the readers in
``bench/metrics/`` read theirs.  Per seed, the tool makes one run of the
cell as ``bench/run.py --trace 1`` does, except that the engine records into
``repro.obs.tracer.Tracer(time.perf_counter, annotate=True)`` and the
trace's idle gaps are named by the innermost of the engine's phase spans.
It prints one JSON line per seed: the result line's per-layer metrics and
breakdown, tokens per second, the two span metrics, each step-shape metric
read from the window's ``_pre_step`` and from the engine's counters, and the
phase split of the traced steps and of the window's slowest step.  The
benchmark's own runs do not read any of this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# host spans that name the trace's idle gaps: the innermost open one wins
HOST_SPANS = (
    "engine.decode", "engine.prefill", "engine.decode.host",
    "engine.decode.wait", "engine.decode.unpack", "engine.prefill.host",
    "engine.prefill.wait", "engine.sample", "engine.sample.wait",
    "bench.admit", "bench.record")
# per-layer metrics computed from each traced step's shapes
STEP_SHAPE_METRICS = ("decode_batch_mean", "kv_used_share", "rollout_mfu",
                      "paged_decode_roofline", "paged_prefill_roofline")


def engine_record(spans, first_traced: int, n_traced: int) -> dict:
    """One entry per ``step()`` (an ``engine.decode`` and the
    ``engine.prefill`` after it): both phases' attrs, their seconds, the
    seconds of their descendants by name and of the ``.wait`` ones (the host
    blocked on the device), and whether the step is one of the ``n_traced``
    from the ``first_traced``-th on; and the seconds of each
    ``engine.queued`` span closed ``served``."""
    by_id = {s.span_id: s for s in spans}
    steps, phase = [], {}               # phase: span id -> its step
    for s in spans:
        if s.name in ("engine.decode", "engine.prefill"):
            if s.name == "engine.decode":
                steps.append({"seconds": 0.0, "wait_s": 0.0, "parts": {},
                              "traced": 0 <= len(steps) - first_traced < n_traced})
            st = phase[s.span_id] = steps[-1]
            st[s.name.split(".")[1]] = s.attrs
            st["seconds"] += s.duration
            continue
        p = s.parent_id
        while p is not None and p not in phase:
            p = by_id[p].parent_id if p in by_id else None
        if p is not None:
            st = phase[p]
            st["parts"][s.name] = st["parts"].get(s.name, 0.0) + s.duration
            if s.name.endswith(".wait"):
                st["wait_s"] += s.duration
    served = [s.duration for s in spans if s.name == "engine.queued"
              and s.closed and s.attrs.get("outcome") == "served"]
    return {"steps": steps, "served_wait_s": served}


def _traced(record):
    return [s for s in (record.get("engine") or {}).get("steps", [])
            if s["traced"]]


def step_host_ms(record):
    """Host time per ``step()`` of the traced stretch that is not spent
    waiting on the device (the phases less their ``.wait`` spans), in ms."""
    steps = _traced(record)
    return (1e3 * sum(s["seconds"] - s["wait_s"] for s in steps) / len(steps)
            if steps else None)


def prefill_wait_ms(record):
    """Admission to first token in the engine, mean over the ``engine.queued``
    spans closed ``served`` in the traced run's whole window, not its
    stretch: too few rows finish prefill inside a 3-s stretch.  In ms."""
    xs = (record.get("engine") or {}).get("served_wait_s")
    return 1e3 * sum(xs) / len(xs) if xs else None


def engine_steps(record):
    """The traced steps' shapes from the engine's counters, in the form of
    ``record["steps"]`` (which ``_pre_step`` builds from outside)."""
    return [{"decode_ctx": s["decode"]["ctx"],
             "prefill_rows": s["prefill"]["rows"],
             "pages_used": s["decode"]["pages_used"],
             "pages_committed": s["decode"]["pages_committed"]}
            for s in _traced(record)]


def split_ms(step: dict) -> dict:
    """One step in ms: host (the ``.host`` children), wait (every
    ``.wait``), unpack, sample (less its wait) and the phases' own rest."""
    part = step["parts"].get
    out = {"host": part("engine.decode.host", 0) + part("engine.prefill.host", 0),
           "wait": step["wait_s"], "unpack": part("engine.decode.unpack", 0),
           "sample": part("engine.sample", 0) - part("engine.sample.wait", 0)}
    out = {"total": step["seconds"], **out,
           "rest": step["seconds"] - sum(out.values())}
    return {k: 1e3 * v for k, v in out.items()}


def measure(cell: dict, seed: int, seconds: float, devs) -> dict:
    """One traced run of ``cell`` with the engine on the program's tracer."""
    from bench import run, spec, trace
    from bench.windows.rollout import Rollout
    from repro.obs.tracer import Tracer

    class Traced(Rollout):
        first_traced = None

        def _pre_step(self):            # the window calls it on traced steps
            if self.first_traced is None:
                self.first_traced = len(self.step_s)
            return super()._pre_step()

        def _record(self, trace_dir):
            rec = super()._record(trace_dir)
            pd = trace.load_planes(trace_dir)
            rec["trace"] = trace.reduce(pd, HOST_SPANS) if pd is not None else {}
            rec["engine"] = engine_record(self.engine.tracer.spans(),
                                          self.first_traced or 0,
                                          len(rec["steps"]))
            return rec

    r = Traced(cell, seed)
    r.engine.tracer = Tracer(time.perf_counter, annotate=True)
    out = r.run(seconds, time.perf_counter(), trace_run=True)
    res = run.result_line(cell, out, devs, True)
    rec = out["record"]
    from_engine = {**rec, "steps": engine_steps(rec)}
    both = {}
    for name in STEP_SHAPE_METRICS:
        if name in cell["metric_files"]:        # the metrics this cell reports
            read = spec.load_module(cell["metric_files"][name]).read
            both[name] = [read(rec), read(from_engine)]
    traced = [split_ms(s) for s in _traced(rec)]
    return {"seed": seed, "correct": res["correct"],
            "rollout_tokens_per_s": out["rollout_tokens_per_s"],
            "compiles_in_window": out["compiles_in_window"],
            "step_host_ms": step_host_ms(rec),
            "prefill_wait_ms": prefill_wait_ms(rec),
            "served": len(rec["engine"]["served_wait_s"]),
            "pre_step_vs_engine": both,
            "traced_split_ms": {k: sum(s[k] for s in traced) / len(traced)
                                for k in traced[0]} if traced else {},
            "slowest_step_ms": max(
                ({**split_ms(s), "new_program": s["decode"]["new_program"]
                  or s["prefill"]["new_program"]}
                 for s in rec["engine"]["steps"]), key=lambda s: s["total"]),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "device": res["device"], "breakdown": res.get("breakdown"),
            "record": rec}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run, spec
    from repro.launch.compile_cache import ENV_VAR, setup_compile_cache

    cell = spec.cell(spec.load(ROOT), args.workload, ROOT)
    devs = run.device_or_exit(cell["chips"])
    os.environ[ENV_VAR] = str(ROOT / ".jax_cache")     # as bench/run.py
    setup_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        m = measure(cell, seed, args.seconds, devs)
        del m["record"]
        print(json.dumps(m), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
