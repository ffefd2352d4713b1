"""A statistic, in ms, over the program's own spans in the whole window
(``lib/spans.py``). One reader for every ``program_span`` metric; params:

- ``names``: the span names measured (exact).
- ``stat``: ``p50`` or ``p95`` over the samples.
- ``per``: ``span`` (default: each span a sample), ``parent`` (the named
  spans that share a parent summed: the two tails of one slice), or
  ``request`` (the named spans of one ``trace_id`` summed, for requests
  whose ``queue`` span, which starts at submit, lies inside the window).
- ``self``: names (or prefixes ending in ``.``) of spans inside the measured
  one, on its thread, whose union is taken off its length: the span's own time.
- ``having``: keep only measured spans with a span of this name inside.

Returns nothing where the window holds no such span.
"""

from ..lib import spans as S
from ..lib.stats import percentile

STATS = {"p50": 50.0, "p95": 95.0}


def measure(spans, params):
    names = set(params["names"])
    picked = [s for s in spans if s.name in names]
    if params.get("having"):
        picked = [s for s in picked if S.inside(s, spans, [params["having"]])]
    own = params.get("self")
    length = {id(s): (s.t1 - s.t0) - (S.covered_seconds(s, S.inside(s, spans, own)) if own else 0.0)
              for s in picked}
    per = params.get("per", "span")
    if per == "span":
        samples = [length[id(s)] for s in picked]
    elif per == "parent":
        groups = {}
        for s in picked:
            if s.parent_id is not None:
                groups[s.parent_id] = groups.get(s.parent_id, 0.0) + length[id(s)]
        samples = list(groups.values())
    elif per == "request":
        submitted = {s.trace_id for s in spans if s.name == "queue" and s.trace_id}
        groups = {}
        for s in picked:
            if s.trace_id in submitted:
                groups[s.trace_id] = groups.get(s.trace_id, 0.0) + length[id(s)]
        samples = list(groups.values())
    else:
        raise ValueError(f"per: {per!r}")
    if not samples:
        return None
    return 1e3 * percentile(samples, STATS[params["stat"]])


def read(ctx, params):
    return measure(S.finished(ctx.t0, ctx.window_t1), params)
