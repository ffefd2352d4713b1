"""An expert model's routing count ``params.attr`` (``moe_held``: pairs on
held experts; ``moe_touched``: held experts with at least one pair) per
EXPERT layer and decode step, over the decode slices of the whole window
(``lib/slice_counts.py``: the ``sched.slice`` spans' attributes). The
sibling of ``slice_moe_rate`` for a model whose leading layers have no
expert layer: the divisor is the family's ``expert_layers(cfg)``, not every
layer of the file. Returns nothing where no slice carries the counts or the
family names no expert layers."""

from ..lib import slice_counts
from ..lib.family import load as family_of


def read(ctx, params):
    layers = getattr(family_of(ctx.cfg), "expert_layers", None)
    found = slice_counts.slices(ctx.t0, ctx.window_t1)
    steps = sum(a["moe_steps"] for a in found)
    if layers is None or not steps:
        return None
    return sum(a[params["attr"]] for a in found) / (steps * layers(ctx.cfg))
