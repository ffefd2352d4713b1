"""Share of the traced window, in %, in which no operation ran on the
device: 1 - union of the device's operation spans / window."""

from ..lib.trace import busy_seconds


def read(ctx, params):
    if ctx.device is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    busy = busy_seconds(ctx.device, ctx.trace_t0, ctx.trace_t1)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (ctx.trace_t1 - ctx.trace_t0))
