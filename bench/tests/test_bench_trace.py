"""The trace reduction on a small synthetic trace of one device."""

from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from bench import trace


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def make_trace():
    host = NS(name="/host:CPU", lines=[line("python3", [
        ev("bench.traced", 0, 1000),
        ev("engine.decode", 0, 420), ev("engine.prefill", 420, 380),
        ev("bench.admit", 800, 100)])])
    dev = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit_fn(1)", 100, 300), ev("jit_fn(2)", 500, 200),
                             ev("jit_fn(3)", 950, 100)]),
        line("XLA Ops", [
            ev("%while.3 = (...) while(...)", 100, 300),
            ev("%paged_decode_attention.11 = bf16[64] custom-call(...)", 120, 150),
            ev("%copy_dynamic-update-slice_fusion.5 = f32[7] fusion(...)", 300, 80),
            ev("%paged_prefill_attention.7 = bf16[1] custom-call(...)", 500, 120),
            ev("%fusion.2 = f32[3] fusion(...)", 650, 50),
            ev("%fusion.9 = f32[3] fusion(...)", 950, 100)])])
    return NS(planes=[host, dev, NS(name="/host:metadata", lines=[])])


def test_reduce_synthetic_trace():
    r = trace.reduce(make_trace())
    assert r["window_s"] == pytest.approx(1e-6)
    d = r["devices"][0]
    # ops cover [100,400], [500,620], [650,700] and, clipped, [950,1000]
    assert d["busy_s"] == pytest.approx(520e-9)
    assert [p["kind"] for p in d["programs"]] == ["decode", "prefill", "other"]
    assert [p["whole"] for p in d["programs"]] == [True, True, False]
    assert d["kernel_s"]["paged_decode_attention"] == pytest.approx(150e-9)
    assert d["kernel_calls"] == {"paged_decode_attention": 1,
                                 "paged_prefill_attention": 1}
    tops = dict(d["top_ops"])
    assert "while" not in tops and tops["fusion"] == pytest.approx(100e-9)
    gaps = dict(d["idle_gaps"])
    # each gap is named by the innermost span open at its middle: [0,100]
    # decode; [400,500] and [620,650] prefill; [700,950] admit (middle 825)
    assert gaps == pytest.approx({"engine.decode": 100e-9,
                                  "engine.prefill": 130e-9,
                                  "bench.admit": 250e-9})


def test_no_window_span_reads_nothing():
    t = make_trace()
    t.planes[0].lines[0].events.pop(0)
    assert trace.reduce(t) == {}


def test_op_stem():
    assert trace.op_stem("%paged_decode_attention.11 = bf16[64,4] x") == \
        "paged_decode_attention"
    assert trace.op_stem("%copy.85 = f32[8]") == "copy"
    assert trace.op_stem("%copy_dynamic-update-slice_fusion.5 = f32") == \
        "copy_dynamic-update-slice_fusion"
