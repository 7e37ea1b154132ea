"""Reduction of a ``jax.profiler`` trace of the traced stretch.

Reads the ``.xplane.pb`` the profiler wrote.  Each device plane
(``/device:TPU:<n>``) has an ``XLA Modules`` line (one event per program
execution) and an ``XLA Ops`` line (one event per HLO operation, nested
inside the loops that run them); the host plane carries the
``TraceAnnotation`` spans the benchmark and the engine opened.  The
reduction gives, per device and clipped to the traced window (the
``bench.traced`` host span):

* busy seconds: the union of the operations' intervals;
* each program execution, classed by the kernels it ran (``decode`` holds
  ``paged_decode_attention``, ``prefill`` holds ``paged_prefill_attention``);
* seconds per kernel, by the name of its custom call;
* the operations that took most time (loops that only contain others are
  left out) and the longest idle gaps, each named by the innermost host
  span open at its middle.
"""

from __future__ import annotations

import glob
import re
from bisect import bisect_right
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced"
KERNELS = {"decode": "paged_decode_attention",
           "prefill": "paged_prefill_attention"}
_CONTAINERS = ("while", "conditional", "call")
_STEM = re.compile(r"^%?([A-Za-z_\-]+?)(?:[._]\d+)*(?:\s|=|$)")


def op_stem(name: str) -> str:
    """'%paged_decode_attention.11 = bf16[...] ...' -> 'paged_decode_attention'."""
    m = _STEM.match(name)
    return m.group(1) if m else name.split(" ")[0]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def load_planes(log_dir: str):
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not files:
        return None
    return ProfileData.from_file(sorted(files)[-1])


def reduce(pd, host_spans=("engine.decode", "engine.prefill",
                           "bench.admit", "bench.record")) -> Dict:
    """Per-device figures of the traced window (seconds)."""
    host: List[Tuple[float, float, str]] = []
    lo = hi = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    lo, hi = e.start_ns, e.start_ns + e.duration_ns
                elif e.name in host_spans:
                    host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if lo is None:
        return {}
    host.sort()

    def label(t: float) -> str:
        open_ = [s for s in host if s[0] <= t < s[1]]
        return min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "host (no span)"

    devices = []
    for plane in pd.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {l.name: list(l.events) for l in plane.lines}
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in lines.get("XLA Ops", [])]
        mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines.get("XLA Modules", [])]
        ops = [o for o in ops if o[1] > lo and o[0] < hi]
        mods = [m for m in mods if m[1] > lo and m[0] < hi]
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns = sum(b - a for a, b in busy)

        starts = [m[0] for m in mods]
        kinds = ["other"] * len(mods)
        kernel_ns: Dict[str, float] = {}
        kernel_calls: Dict[str, int] = {}
        per_op: Dict[str, float] = {}
        for a, b, name in ops:
            stem = op_stem(name)
            i = bisect_right(starts, a) - 1
            for kind, kname in KERNELS.items():
                if stem == kname:
                    kernel_ns[kname] = kernel_ns.get(kname, 0.0) + (b - a)
                    kernel_calls[kname] = kernel_calls.get(kname, 0) + 1
                    if 0 <= i < len(mods) and mods[i][0] <= a < mods[i][1]:
                        kinds[i] = kind
            if stem not in _CONTAINERS:
                ca, cb = max(a, lo), min(b, hi)
                if cb > ca:
                    per_op[stem] = per_op.get(stem, 0.0) + (cb - ca)
        programs = [{"kind": k, "start_ns": m[0], "seconds": (m[1] - m[0]) / 1e9,
                     "whole": lo <= m[0] and m[1] <= hi}
                    for k, m in zip(kinds, mods)]
        gaps = []
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((a - prev, label((a + prev) / 2)))
            prev = max(prev, b)
        by_label: Dict[str, float] = {}
        for g, name in gaps:
            by_label[name] = by_label.get(name, 0.0) + g
        devices.append({
            "device": plane.name,
            "busy_s": busy_ns / 1e9,
            "programs": programs,
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "kernel_calls": kernel_calls,
            "top_ops": sorted(((k, v / 1e9) for k, v in per_op.items()),
                              key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(((k, v / 1e9) for k, v in by_label.items()),
                                key=lambda kv: -kv[1])[:10],
        })
    return {"window_s": (hi - lo) / 1e9, "window_ns": (lo, hi),
            "devices": devices}
