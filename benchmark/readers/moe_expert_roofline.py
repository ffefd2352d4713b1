"""Share of the HBM roofline the held experts' matmuls reach, in %.

Numerator: the least time the chip could take to read the experts a decode
step touched: the family's ``expert_bytes`` of the held experts that had at
least one token-expert pair, per step, from the ``sched.slice`` spans'
``moe_touched`` and ``moe_steps`` over the traced part of the window (a held
expert nobody chose need not be read; one that is chosen is read once),
at the table's HBM rate. Denominator: the device time, per step, of the
decode-slice program's operations whose innermost scope is ``moe.experts``
(``lib/scope_paths.py``): the grouped matmuls, without the router, the
dispatch's sort and gather, or the combine's scatter. Bytes bound it: a
block of 8 pairs against 37.75 MB of weights. The program reads an expert
once per block of 8 pairs, so the share can fall below what the counted
bytes allow; it cannot rise above."""

from ..lib import scope_paths, slice_counts
from ..lib.family import load as family_of


def read(ctx, params):
    if ctx.device is None or ctx.chip is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    found = scope_paths.seconds_under(params["module"], ctx.trace_t0, ctx.trace_t1, params["scopes"])
    slices = slice_counts.slices(ctx.t0, ctx.t1)
    steps = sum(a["moe_steps"] for a in slices)
    if found is None or not found[0] or not steps:
        return None
    seconds, runs = found
    touched = sum(a["moe_touched"] for a in slices) / steps  # over all layers, per step
    need = family_of(ctx.cfg).expert_bytes(ctx.cfg, touched) / float(ctx.chip["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / (runs * ctx.slice_steps))
