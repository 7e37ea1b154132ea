"""Structured spans on whatever clock the plane already runs on.

The sim backend lives on the event clock (``EventLoop.now``); the real
engine's work is wall time.  A :class:`Tracer` takes its clock as a
callable, so both record through the same API and the exporter never
cares which world produced a span.

Spans are parent/child linked (``span_id`` / ``parent_id``) and carry
free-form attrs — by convention ``req`` / ``group`` / ``inst`` ids, so a
request's life (prefill chunks, decode horizons, KV export/import,
migrations) can be stitched across lanes.  Recording is a bounded ring
buffer (``collections.deque(maxlen=...)``); an optional JSONL sink
streams closed spans to disk for runs larger than the ring.

Hot paths hold a tracer unconditionally and call it unconditionally —
the **null tracer** (module singleton :data:`NULL_TRACER`) makes the
disabled case a constant-time no-op method call, which is what keeps
the "recording off" overhead at ~0 (guarded by ``bench_obs``).

``Tracer(time.perf_counter, annotate=True)`` also opens a
``jax.profiler.TraceAnnotation`` named after each span (attrs stay in the
ring), so under ``jax.profiler.start_trace`` the real engine's spans land
on the profiler's host plane, on the device trace's clock.

Span taxonomy (ROADMAP "Telemetry plane" notes):

  instance lanes (``inst:N``): ``prefill.chunk``, ``decode.horizon``,
    ``pull.weights``, ``migrate.import``, ``seed.window``; instants
    ``swap.weights``, ``migrate.export``, ``preempt.grace``,
    ``instance.dead``
  NIC lanes (``nic:AGENT``): ``transfer.chunk`` (parent = the owning
    pull's span)
  trainer lane (``trainer``): ``rl.step``, ``train.microbatch``,
    ``collect.flush`` (streamed collection: tail-flush window whose
    preprocess share overlapped the rollout)
  engine lanes (real backend, wall clock): per ``step()``,
    ``engine.decode`` (attrs ``rows``, ``ctx``, ``pages_used``,
    ``pages_committed``, ``new_program``) > ``engine.decode.host``,
    ``engine.decode.wait``, ``engine.decode.unpack``; ``engine.prefill``
    (attrs ``rows``, ``new_program``) > ``engine.prefill.host``,
    ``engine.prefill.wait``, ``engine.sample`` > ``engine.sample.wait``.
    A name ending in ``.wait`` is the host blocked on the device, and
    nothing else.  Per request, ``engine.queued``
    (admission to first token; closed ``outcome=served`` or ``dropped``;
    never annotated).  Between steps ``engine.swap_weights`` (instant),
    ``engine.kv_export``, ``engine.kv_import`` (attr ``n_pages``)
"""

from __future__ import annotations

import json
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional


@dataclass
class Span:
    name: str
    t0: float
    lane: str
    span_id: int
    parent_id: Optional[int] = None
    t1: Optional[float] = None
    attrs: Dict = field(default_factory=dict)

    @property
    def closed(self) -> bool:
        return self.t1 is not None

    @property
    def duration(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0

    def to_dict(self) -> Dict:
        return dict(name=self.name, t0=self.t0, t1=self.t1, lane=self.lane,
                    span_id=self.span_id, parent_id=self.parent_id,
                    attrs=self.attrs)


class Tracer:
    """Span recorder over a caller-supplied clock.

    ``clock`` — ``EventLoop.now`` getter for the sim world,
    ``time.perf_counter`` for the real engine.  ``capacity`` bounds the
    ring buffer; ``jsonl_path`` additionally streams every CLOSED span
    as one JSON line (instants close immediately).  ``annotate`` opens a
    profiler annotation per span (see the module docstring); a span opened
    with ``annotate=False`` gets none, for spans that outlive the host
    work they would otherwise label."""

    enabled = True

    def __init__(self, clock: Callable[[], float], *,
                 capacity: int = 65536,
                 jsonl_path: Optional[str] = None,
                 annotate: bool = False):
        self.clock = clock
        self._spans: deque = deque(maxlen=capacity)
        self._next_id = 0
        self._jsonl = open(jsonl_path, "w") if jsonl_path else None
        self._annotation = None
        if annotate:                    # the sim's tracers never import JAX
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._open_annotations: Dict[int, object] = {}   # by span_id

    # ---------------- recording ---------------- #
    def begin(self, name: str, lane: str, *,
              parent: Optional[Span] = None,
              t0: Optional[float] = None, annotate: bool = True,
              **attrs) -> Span:
        """Open a span.  ``t0`` overrides the clock for retroactive spans
        (the sim emits a fused step's prefill/decode spans when the step
        *fires*, back-dating them to when it was scheduled)."""
        self._next_id += 1
        if self._annotation is not None and annotate:
            a = self._annotation(name)
            a.__enter__()
            self._open_annotations[self._next_id] = a
        s = Span(name=name, t0=self.clock() if t0 is None else t0,
                 lane=lane, span_id=self._next_id,
                 parent_id=(parent.span_id if parent is not None else None),
                 attrs=attrs)
        self._spans.append(s)
        return s

    def end(self, span: Span, *, t1: Optional[float] = None,
            **attrs) -> Span:
        if span.t1 is None:             # idempotent on double-close
            span.t1 = self.clock() if t1 is None else t1
            a = self._open_annotations.pop(span.span_id, None)
            if a is not None:
                a.__exit__(None, None, None)
            if attrs:
                span.attrs.update(attrs)
            self._sink(span)
        return span

    def event(self, name: str, lane: str, *,
              parent: Optional[Span] = None, **attrs) -> Span:
        """Zero-duration instant (t1 == t0): swaps, grace notices, kills."""
        s = self.begin(name, lane, parent=parent, **attrs)
        return self.end(s, t1=s.t0)

    @contextmanager
    def span(self, name: str, lane: str, *,
             parent: Optional[Span] = None, **attrs):
        s = self.begin(name, lane, parent=parent, **attrs)
        try:
            yield s
        finally:
            self.end(s)

    # ---------------- reading ---------------- #
    def spans(self) -> List[Span]:
        return list(self._spans)

    def lanes(self) -> List[str]:
        seen: Dict[str, None] = {}
        for s in self._spans:
            seen.setdefault(s.lane)
        return list(seen)

    def _sink(self, span: Span):
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(span.to_dict()) + "\n")

    def close(self):
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None


class _NullTracer(Tracer):
    """Recording off: every call is a constant-time no-op returning one
    shared dummy span, so instrumented hot paths need no ``if`` guards."""

    enabled = False

    def __init__(self):
        super().__init__(lambda: 0.0, capacity=1)
        self._dummy = Span("", 0.0, "", 0, t1=0.0)
        self._null_span = nullcontext(self._dummy)

    def begin(self, name, lane, *, parent=None, t0=None, annotate=True,
              **attrs):
        return self._dummy

    def end(self, span, *, t1=None, **attrs):
        return span

    def event(self, name, lane, *, parent=None, **attrs):
        return self._dummy

    def span(self, name, lane, *, parent=None, **attrs):
        return self._null_span

    def spans(self):
        return []


NULL_TRACER = _NullTracer()
