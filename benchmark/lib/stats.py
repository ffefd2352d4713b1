"""Metric arithmetic over the consumer-side event log. Everything is
taken over the whole window: all tokens, all requests, all gaps."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


@dataclass
class Record:
    """What one request's consumer saw, on the benchmark's own clock
    (``time.monotonic`` seconds)."""

    index: int
    prompt_tokens: int
    output_tokens: int  # asked for
    t_due: float  # open loop: when it was due; closed loop: t_submit
    t_submit: float
    events: List[Tuple[float, int]] = field(default_factory=list)  # (t, new tokens)
    tokens: List[int] = field(default_factory=list)
    t_done: Optional[float] = None
    error: Optional[str] = None
    prompt: str = ""


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (``q`` in 0..100) of a non-empty
    sequence."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def slices_within(slices: Sequence[Tuple[float, float, int]], t0: float, t1: float):
    """The ``(t_end, gap_s, rows)`` slice gaps that lie whole inside ``[t0, t1]``."""
    return [s for s in slices if t0 <= s[0] - s[1] and s[0] <= t1]


def window_metrics(
    records: Sequence[Record], t0: float, t1: float
) -> Dict[str, Dict[str, float]]:
    """The end-to-end numbers of the window ``[t0, t1)`` and the sample
    counts behind them.

    - ``tokens_per_s``: every output token an event delivered inside the
      window, over the window's seconds.
    - ``ttft_ms``: for each request whose first event came inside the
      window, first event minus when it was due (closed loop: submitted).
    - ``stream_gap_ms``: every gap between consecutive events of one
      request whose later event came inside the window.
    """
    tokens = 0
    ttft: List[float] = []
    gaps: List[float] = []
    for rec in records:
        prev = None
        for t, n in rec.events:
            if t0 <= t < t1:
                tokens += n
                if prev is None:
                    ttft.append((t - rec.t_due) * 1e3)
                else:
                    gaps.append((t - prev) * 1e3)
            prev = t
    attempted = [r for r in records if t0 <= r.t_due < t1]
    return {
        "tokens_per_s": {"value": tokens / (t1 - t0), "samples": tokens},
        "ttft_ms": {"values": ttft},
        "stream_gap_ms": {"values": gaps},
        "attempted": len(attempted),
        "failed": sum(1 for r in attempted if r.error is not None),
    }
