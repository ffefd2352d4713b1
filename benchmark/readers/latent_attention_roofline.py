"""Share of the HBM roofline that decode attention over the latent cache
reaches, in %.

Numerator: the least time the chip could take to read the cached rows the
live rows' contexts hold, every attention block of every layer, per decode
step: the family's ``latent_bytes`` of the contexts the ``sched.slice`` spans
report over the traced part of the window (``ctx_tokens`` before the slice
plus half of what the slice produced, ``moe_tokens``), at the table's HBM
rate. Denominator: the device time, per step, of the decode-slice program's
operations whose innermost scope is ``attn.kv_gather`` or ``attn.core``
(``lib/scope_paths.py``): the page gather and the side cache's read, scores,
softmax and values. Padding rows, unreal table pages and the row's padding
lanes are not counted: they are the gap."""

from ..lib import scope_paths, slice_counts
from ..lib.family import load as family_of


def read(ctx, params):
    if ctx.device is None or ctx.chip is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    found = scope_paths.seconds_under(params["module"], ctx.trace_t0, ctx.trace_t1, params["scopes"])
    slices = [a for a in slice_counts.slices(ctx.t0, ctx.t1) if a.get("ctx_tokens") is not None]
    steps = sum(a["moe_steps"] for a in slices)
    if found is None or not found[0] or not steps:
        return None
    seconds, runs = found
    context = sum(a["moe_steps"] * (a["ctx_tokens"] + a["moe_tokens"] / 2.0) for a in slices) / steps
    need = family_of(ctx.cfg).latent_bytes(ctx.cfg, context) / float(ctx.chip["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / (runs * ctx.slice_steps))
