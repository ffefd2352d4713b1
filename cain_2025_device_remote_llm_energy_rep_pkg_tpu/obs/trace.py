"""Lightweight span tracer: monotonic-clock spans with parent links.

The runner already has *device*-side tracing (``profilers/jax_trace.py``
wraps ``jax.profiler``; ``benchmark/lib/trace.py`` reduces its XLA-Ops
spans) but nothing host-side: a served request's life — HTTP accept →
scheduler queue → grouped prefill → batched decode — was invisible.
These spans are the host half: cheap (one ``time.monotonic()`` pair and
a list append per span), thread-safe, and exportable as Chrome trace
events (the ``traceEvents`` JSON that chrome://tracing, Perfetto and
TensorBoard's trace viewer all read — the same format family as the
``jax_trace`` artifacts the analysis harness already consumes).

Parenting: a thread-local stack tracks the current span per thread;
spans opened within another nest automatically. Requests that hop
threads (HTTP handler → BatchScheduler loop) carry their root span on
the ticket and the executing thread re-enters it with :meth:`SpanTracer.
attach`, so the queue→prefill→decode children land under the right
request even though three threads touched it.

Two sinks, one call: a live :meth:`SpanTracer.span` also enters a
``jax.profiler.TraceAnnotation`` of the same name for its life, so the
span lies on the host plane of a profiler trace (``*.xplane.pb``) beside
the device's operations, on the profiler's clock, while the in-memory
ring keeps it on ``time.monotonic``. ``jax`` is only looked up when the
process has already imported it (``sys.modules``): the fake-engine server
stays free of it. Span names are plain dotted words (``sched.iter``,
``session.slice.wait``): no ``(``, no trailing digits or dots, so a trace
reducer that strips operation ids leaves them whole. Already-timed
intervals (:meth:`SpanTracer.add_span`) and detached roots
(:meth:`SpanTracer.root`) reach the ring only: they belong to no one
thread, and an annotation opens and closes on the thread that runs it.

Honors the same kill switch as the metrics registry
(``obs.metrics.enabled``): disabled means zero spans recorded and zero
annotations entered.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import sys
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

from .metrics import enabled

# Finished-span ring: bounds memory for long-running servers (a span is
# ~200 bytes; 50k ≈ 10 MB worst case). Consumers that need everything
# (SpanTraceProfiler) drain within a run window, far below the cap.
MAX_SPANS = 50_000


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """The FLEET-WIDE trace context a request carries across process
    boundaries (wire field ``x_trace``, ISSUE 13): a trace id shared by
    every hop the request touches — front-door router, each dispatch
    attempt's replica, the replica's scheduler and stepped session —
    plus the parent span id of the hop that forwarded it, so a
    cross-process timeline can link a replica's span tree back to the
    router's. Span ids stay process-local (ints minted per tracer);
    ``trace_id`` is the one identifier that is globally meaningful."""

    trace_id: str
    parent: Optional[str] = None  # forwarding hop's span id (stringed)


def mint_trace_id() -> str:
    """A fresh 16-hex-char fleet-wide trace id (random, collision-safe
    at serving volumes; callers — router front door, load generators —
    mint once per request and every retry attempt REUSES it)."""
    return uuid.uuid4().hex[:16]


def _annotation(name: str, attrs: Dict[str, Any]):
    """An entered ``jax.profiler.TraceAnnotation`` for a live span, or
    None where the process has not imported jax. Outside a profiler
    session an annotation costs about a microsecond."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(name, **attrs)
        ann.__enter__()
    except Exception:  # noqa: BLE001 — a half-imported jax, an odd attr
        return None
    return ann


class Span:
    """One finished (or in-flight) span. ``dur_s`` is None while open.
    ``trace_id`` is the fleet-wide trace the span belongs to (inherited
    from the parent span unless set explicitly at the request root)."""

    __slots__ = (
        "name", "span_id", "parent_id", "t0_s", "dur_s", "tid", "attrs",
        "seq", "trace_id",
    )

    def __init__(
        self,
        name: str,
        span_id: int,
        parent_id: Optional[int],
        t0_s: float,
        tid: int,
        attrs: Optional[Dict[str, Any]],
        trace_id: Optional[str] = None,
    ) -> None:
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_s = t0_s
        self.dur_s: Optional[float] = None
        self.tid = tid
        self.attrs = attrs or {}
        self.seq = 0  # assigned at close
        self.trace_id = trace_id


class _SpanCtx:
    """Context manager for an open span (also usable as a parent handle)."""

    __slots__ = ("_tracer", "span", "_ann")

    def __init__(
        self, tracer: "SpanTracer", span: Optional[Span], ann=None
    ) -> None:
        self._tracer = tracer
        self.span = span
        self._ann = ann  # the profiler-side twin (None: no jax, or off)

    def __enter__(self) -> Optional[Span]:
        return self.span

    def __exit__(self, *exc) -> None:
        if self.span is not None:
            self._tracer._close(self.span)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return None


class _AttachCtx:
    """Re-enter an existing span as the current thread's parent (cross-
    thread continuation). Does NOT close the span on exit."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "SpanTracer", span: Optional[Span]) -> None:
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Optional[Span]:
        if self._span is not None:
            self._tracer._stack().append(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            stack = self._tracer._stack()
            if stack and stack[-1] is self._span:
                stack.pop()
        return None


class SpanTracer:
    def __init__(self, max_spans: int = MAX_SPANS) -> None:
        self._ids = itertools.count(1)
        self._seq = itertools.count(1)
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(maxlen=max_spans)
        self._tls = threading.local()
        self._last_seq = 0

    # -- internals ------------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _close(self, span: Span, t1_s: Optional[float] = None) -> None:
        now = time.monotonic() if t1_s is None else t1_s
        span.dur_s = max(now - span.t0_s, 0.0)
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        with self._lock:
            span.seq = next(self._seq)
            self._last_seq = span.seq
            self._spans.append(span)

    # -- public surface -------------------------------------------------------
    def span(
        self, name: str, trace_id: Optional[str] = None, **attrs: Any
    ) -> _SpanCtx:
        """Open a span as a context manager, nested under the thread's
        current span (if any), and enter a profiler annotation of the
        same name and attrs (module docstring). No-op (yields None, no
        annotation) when disabled. ``trace_id`` stamps the fleet-wide
        trace at a request ROOT; nested spans inherit the parent's
        automatically."""
        if not enabled():
            return _SpanCtx(self, None)
        stack = self._stack()
        parent = stack[-1] if stack else None
        span = Span(
            name, next(self._ids),
            parent.span_id if parent is not None else None,
            time.monotonic(), threading.get_ident(), attrs,
            trace_id=trace_id
            or (parent.trace_id if parent is not None else None),
        )
        stack.append(span)
        return _SpanCtx(self, span, _annotation(name, attrs))

    def root(
        self, name: str, trace_id: Optional[str] = None, **attrs: Any
    ) -> Optional[Span]:
        """Open a DETACHED root: a span with no parent that is NOT left
        on the caller's thread stack, for an owner that outlives the
        call (a scheduler ticket: ``submit_stream`` returns at once and
        another thread finishes the request). Children hang under it by
        ``add_span(parent=...)`` or ``attach``; :meth:`finish` closes
        it. Ring only: no one thread runs it, so no annotation. None
        when disabled."""
        if not enabled():
            return None
        return Span(
            name, next(self._ids), None, time.monotonic(),
            threading.get_ident(), attrs, trace_id=trace_id,
        )

    def finish(self, span: Optional[Span], t1_s: Optional[float] = None) -> None:
        """Close a :meth:`root` (idempotent; None is a no-op)."""
        if span is not None and span.dur_s is None:
            self._close(span, t1_s)

    def attach(self, span: Optional[Span]) -> _AttachCtx:
        """Make ``span`` the current parent on THIS thread for the body
        of the with-block (cross-thread request continuation). Accepts
        None (no-op) so callers can pass tickets' maybe-absent roots."""
        if not enabled():
            span = None
        return _AttachCtx(self, span)

    def current(self) -> Optional[Span]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def add_span(
        self,
        name: str,
        t0_s: float,
        t1_s: float,
        attrs: Optional[Dict[str, Any]] = None,
        parent: Optional[Span] = None,
    ) -> Optional[Span]:
        """Record an already-timed interval (the engine fence-times its
        prefill/decode windows anyway — re-wrapping them in live spans
        would double the clock reads). ``parent`` overrides the thread's
        current span."""
        if not enabled():
            return None
        if parent is None:
            parent = self.current()
        span = Span(
            name, next(self._ids),
            parent.span_id if parent is not None else None,
            t0_s, threading.get_ident(), attrs,
            trace_id=parent.trace_id if parent is not None else None,
        )
        span.dur_s = max(t1_s - t0_s, 0.0)
        with self._lock:
            span.seq = next(self._seq)
            self._last_seq = span.seq
            self._spans.append(span)
        return span

    def seq(self) -> int:
        """High-water mark for :meth:`spans`' ``since`` (run windowing)."""
        with self._lock:
            return self._last_seq

    def spans(self, since: int = 0) -> List[Span]:
        """Finished spans recorded after sequence number ``since``."""
        with self._lock:
            return [s for s in self._spans if s.seq > since]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # -- export ---------------------------------------------------------------
    def chrome_trace(self, spans: Optional[List[Span]] = None) -> Dict[str, Any]:
        """Chrome trace-event JSON (``ph: "X"`` complete events, µs
        timebase) with parent ids in ``args`` — loadable in
        chrome://tracing / Perfetto next to the ``jax_trace`` device
        traces."""
        if spans is None:
            spans = self.spans()
        events = []
        for s in spans:
            args = dict(s.attrs)
            args["span_id"] = s.span_id
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            if s.trace_id is not None:
                args["trace_id"] = s.trace_id
            events.append(
                {
                    "name": s.name,
                    "ph": "X",
                    "ts": round(s.t0_s * 1e6, 3),
                    "dur": round((s.dur_s or 0.0) * 1e6, 3),
                    "pid": 1,
                    "tid": s.tid,
                    "args": args,
                }
            )
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path, spans: Optional[List[Span]] = None) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(spans), f, indent=1)


# THE process-wide tracer every instrumented module shares.
TRACER = SpanTracer()
