"""Of the device's idle seconds in gaps of ``min_gap_ms`` or more inside
the traced window, the share, in %, that a phase span of the program on
the profiler's host plane covers for more than half of the gap: how much
of the idle time the program accounts for by name.

A phase span is one whose name starts with one of ``prefixes`` and is not
in ``exclude`` (``sched.iter`` is the whole pass and names nothing). The
program writes them through ``jax.profiler.TraceAnnotation``
(``obs/trace.py``), so they lie beside the device's operations on one
clock. Returns nothing where the trace holds no such span (an older
program) or no such gap.

The profiler keeps an annotation only if it opened and closed inside the
profiling session, so the pass that straddles either edge of the traced
window is lost with whatever phase it was in, and the gaps under it could
never be named. Gaps are therefore taken between the start of the first
and the end of the last span of the program (``sched.iter`` included) that
the trace holds inside the window: the part a span could cover.
"""

from ..lib.trace import idle_gaps


def read(ctx, params):
    if ctx.device is None or ctx.trace is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    prefixes = tuple(params["prefixes"])
    exclude = set(params.get("exclude", ()))
    ours = [s for s in ctx.trace.host
            if s[2].startswith(prefixes) and s[0] >= ctx.trace_t0 and s[1] <= ctx.trace_t1]
    phases = [s for s in ours if s[2] not in exclude]
    if not phases:
        return None
    lo, hi = min(s[0] for s in ours), max(s[1] for s in ours)
    least = float(params.get("min_gap_ms", 1.0)) * 1e-3
    total = named = 0.0
    for a, b in idle_gaps(ctx.device.ops, lo, hi):
        if b - a < least:
            continue
        total += b - a
        for s, e, _ in phases:  # sorted by start
            if s >= b:
                break
            if min(e, b) - max(s, a) > 0.5 * (b - a):
                named += b - a
                break
    if total <= 0:
        return None
    return 100.0 * named / total
