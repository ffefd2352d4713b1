"""Median gap between the ends of consecutive decode slices, in ms: what a
resident row waits for its next 16 tokens, join chunk included."""

from ..lib.stats import percentile


def read(ctx, params):
    gaps = [gap * 1e3 for _, gap, _ in ctx.slices]
    return percentile(gaps, 50) if gaps else None
