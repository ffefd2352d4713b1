"""The program's own spans, for the ``program_span`` readers: beside
``system.py`` the only file of the benchmark that touches the program.

The program's process-wide ``TRACER`` (``obs/trace.py``) keeps every
finished span in an in-memory ring on ``time.monotonic``, the clock of
``Context.t0`` and ``window_t1``. The readers run in the process that
served, after ``sut.close()``, so the ring still holds the whole window.
Spans come back as plain tuples; nothing of the program's leaves this file.

Span names the readers know (PERF.md §3 has the table):

- per request, under a ``request`` root, sharing its ``trace_id``: ``queue``,
  ``join.wait``, ``join.prefill``, ``join.commit``, ``egress.first`` (or
  ``queue``, ``open``): they tile submit -> first token;
- per pass of the scheduler's loop: ``sched.iter`` and inside it
  ``sched.reap``, ``sched.slice``, ``sched.egress``, ``sched.join``,
  ``sched.admit``, ``sched.sweep``, with the session's ``session.slice.*``
  and ``session.join.*`` inside those, all on the scheduler's one thread.

A program without such spans (an older commit) gives a list without these
names, and every reader then finds nothing to read.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple


class S(NamedTuple):
    name: str
    t0: float  # time.monotonic seconds
    t1: float
    tid: int  # thread that recorded it
    span_id: int
    parent_id: Optional[int]
    trace_id: Optional[str]
    attrs: Dict[str, Any]


_CACHE: Dict[Tuple[float, float], List[S]] = {}


def finished(t0: float, t1: float) -> List[S]:
    """The tracer's finished spans that lie whole inside ``[t0, t1]``,
    by start time. Empty where the program has no tracer or it is off."""
    key = (t0, t1)
    if key not in _CACHE:
        try:
            from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER

            raw = TRACER.spans()
        except Exception:  # a program without the tracer: nothing to read
            raw = []
        out = []
        for s in raw:
            if s.dur_s is None or s.t0_s < t0 or s.t0_s + s.dur_s > t1:
                continue
            out.append(S(s.name, s.t0_s, s.t0_s + s.dur_s, s.tid, s.span_id, s.parent_id,
                         getattr(s, "trace_id", None), dict(s.attrs or {})))
        out.sort(key=lambda s: (s.t0, -s.t1))
        _CACHE.clear()  # one window a process, as a rule: keep the newest only
        _CACHE[key] = out
    return _CACHE[key]


def covered_seconds(outer: S, inner: Sequence[S]) -> float:
    """Seconds of ``outer`` that the union of ``inner`` covers (spans
    nest, so the union, never the sum)."""
    total, end = 0.0, outer.t0
    for s in sorted(inner, key=lambda s: s.t0):
        a, b = max(s.t0, end), min(s.t1, outer.t1)
        if b > a:
            total += b - a
            end = b
    return total


def inside(outer: S, spans: Sequence[S], prefixes: Sequence[str]) -> List[S]:
    """The spans of ``outer``'s thread, named by one of ``prefixes``, that
    lie inside it in time. Containment in time, not the parent link: the
    scheduler re-enters a request's root around the session's join calls
    (``attach``), so ``session.join.*`` hang under the request while they
    run inside ``sched.join``."""
    return [s for s in spans
            if s.tid == outer.tid and s is not outer and s.t0 >= outer.t0 and s.t1 <= outer.t1
            and any(s.name == p or (p.endswith(".") and s.name.startswith(p)) for p in prefixes)]
