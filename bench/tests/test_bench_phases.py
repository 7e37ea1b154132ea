"""The engine's own spans on a traced run (``bench/tools/phases.py``): its
step counters are what the window's ``_pre_step`` reads from outside, its
two span metrics read above zero, and its phase children name idle gaps."""

from __future__ import annotations

import jax
import pytest

from bench import peaks, spec, trace
from bench.tests import tiny
from bench.tests.test_bench_trace import ev, make_trace
from bench.tools import phases


def test_innermost_phase_names_an_idle_gap():
    t = make_trace()
    t.planes[0].lines[0].events += [ev("engine.decode.host", 0, 90),
                                    ev("engine.prefill.wait", 440, 200)]
    gaps = dict(trace.reduce(t, phases.HOST_SPANS)["devices"][0]["idle_gaps"])
    # [0,100] has its middle in decode.host; [400,500] and [620,650] in
    # prefill.wait; [700,950] in bench.admit
    assert gaps == pytest.approx({"engine.decode.host": 100e-9,
                                  "engine.prefill.wait": 130e-9,
                                  "bench.admit": 250e-9})


def test_engine_counters_are_what_the_window_reads(tmp_path, monkeypatch):
    root = tiny.make_root(tmp_path, 0.25)
    monkeypatch.setattr(peaks, "for_kind",
                        lambda kind: {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    cell = spec.cell(spec.load(root), tiny.CELL, root)
    m = phases.measure(cell, 2**33 + 7, 2.0, jax.devices()[:1])
    assert m["correct"] is True
    assert m["step_host_ms"] > 0 and m["prefill_wait_ms"] > 0
    assert m["slowest_step_ms"]["total"] >= m["slowest_step_ms"]["wait"] > 0
    rec = m["record"]
    traced = [s for s in rec["engine"]["steps"] if s["traced"]]
    assert len(traced) == len(rec["steps"]) > 0
    for s, e in zip(rec["steps"], traced):
        d, p = e["decode"], e["prefill"]
        assert d["rows"] == len(s["decode_ctx"]) and d["ctx"] == s["decode_ctx"]
        assert d["pages_used"] == s["pages_used"]
        assert d["pages_committed"] == s["pages_committed"]
        assert p["rows"] == s["prefill_rows"]
    # the step-shape metrics read the same from the engine's counters (the
    # rooflines read None here: the CPU trace holds no TPU kernel)
    both = m["pre_step_vs_engine"]
    assert None not in both["decode_batch_mean"] + both["kv_used_share"]
    for name, (pre_step, engine) in both.items():
        assert engine == pre_step, name
