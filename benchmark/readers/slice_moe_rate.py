"""An expert model's routing count ``params.attr`` (``moe_held``: pairs on
held experts; ``moe_touched``: held experts with at least one pair) per
layer and decode step, over the decode slices of the whole window
(``lib/slice_counts.py``: the ``sched.slice`` spans' attributes). Returns
nothing where no slice carries the counts."""

from ..lib import slice_counts


def read(ctx, params):
    found = slice_counts.slices(ctx.t0, ctx.window_t1)
    steps = sum(a["moe_steps"] for a in found)
    if not steps:
        return None
    return sum(a[params["attr"]] for a in found) / (steps * slice_counts.layers(ctx.cfg))
