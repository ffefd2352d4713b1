"""A sum over the program's own spans in the whole window (``lib/spans.py``),
per second of window or as a share: how much of a second of serving went to
something the program itself accounted for. Params:

- ``names``: the span names summed over (exact).
- ``attr``: the numeric attribute summed; absent: the spans' own seconds.
  A span that lacks the attribute (or holds ``None``: the platform has no
  such counter) adds nothing.
- ``where``: ``{"attr": a, "is_not": v}``: only spans whose attribute ``a``
  is not ``v`` count.
- ``as``: ``ms_per_s`` (default): the sum, in ms, per second of window;
  ``share_pct``: the sum under ``where`` over the sum without it, in %.
- ``witness``: ``{"names": [...], "attr": a}``: spans that only a program
  which writes the measured ones has. With a witness in the window and no
  measured span, the sum is 0 (a share 100: nothing fell outside); without
  either the reader finds nothing to read.

Returns nothing where the window holds neither (an older program, telemetry
off), or where no measured span carries a number under ``attr``.
"""

from ..lib import spans as S


def _value(span, attr):
    if attr is None:
        return span.t1 - span.t0
    v = span.attrs.get(attr)
    return float(v) if isinstance(v, (int, float)) and not isinstance(v, bool) else None


def _passes(span, where):
    return not where or span.attrs.get(where["attr"]) != where["is_not"]


def measure(spans, params, window_s):
    names = set(params["names"])
    attr = params.get("attr")
    where = params.get("where")
    share = params.get("as", "ms_per_s") == "share_pct"
    total = picked = 0.0
    seen = False
    for s in spans:
        if s.name not in names:
            continue
        v = _value(s, attr)
        if v is None:
            continue
        seen = True
        total += v
        if _passes(s, where):
            picked += v
    if not seen:
        w = params.get("witness")
        if not w or not any(s.name in w["names"] and w["attr"] in s.attrs for s in spans):
            return None
    if share:
        return 100.0 * picked / total if total > 0 else 100.0
    if window_s <= 0:
        return None
    return 1e3 * picked / window_s


def read(ctx, params):
    return measure(S.finished(ctx.t0, ctx.window_t1), params, ctx.window_t1 - ctx.t0)
