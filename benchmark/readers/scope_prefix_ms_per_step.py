"""Device time, in ms per decode step, of the operations of one program
(``params.module``) that lie under a ``jax.named_scope`` whose name starts
with one of ``params.prefixes`` (``hc.``: the residual-stream maps), wherever
on the operation's ``op_name`` path that scope is. The sibling of
``scope_path_ms_per_step`` for scopes that neither ``lib/scopes.py``'s
accepted list nor ``lib/scope_paths.py``'s holds: the same trace
(``scopes.load``: the busiest TPU plane's operations with their ``op_name``
paths), the runs of the program whole inside the traced part, operations
that only wrap others left out. A step is one of a run's ``slice_steps``.
Returns nothing where there is no trace or the trace has no run of the
program."""

import re
from bisect import bisect_right

from ..lib import scopes
from ..lib.trace import WRAPPERS, find_xplane, strip_id

_DEVICE = {}


def under(op_name, prefixes):
    """Whether any scope on the path (the stat reads ``op_name:op_type``) starts with a prefix."""
    return any(part.startswith(p) for part in op_name.split(":")[0].split("/") for p in prefixes)


def reduce(dev, module, t0, t1, prefixes):
    """``(seconds under the prefixes, runs)`` over the runs, whole inside ``[t0, t1]``, of the programs matching ``module``."""
    rx = re.compile(module)
    runs = [(a, b) for a, b, n in dev.modules if a >= t0 and b <= t1 and rx.search(strip_id(n))]
    starts = [a for a, _, _ in dev.ops]
    hit = 0.0
    for a, b in runs:
        for s, e, mid in dev.ops[bisect_right(starts, a - 1e-12):]:
            if s >= b:
                break
            name, op_name = dev.meta.get(mid, ("", ""))
            if strip_id(name) not in WRAPPERS and under(op_name, prefixes):
                hit += e - s
    return hit, len(runs)


def read(ctx, params):
    if ctx.device is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    path = find_xplane(scopes.TRACE_DIR)
    if path is None:
        return None
    key = str(path)
    if key not in _DEVICE:  # read once per process
        _DEVICE.clear()
        _DEVICE[key] = scopes.load(path)
    dev = _DEVICE[key]
    if dev is None:
        return None
    seconds, runs = reduce(dev, params["module"], ctx.trace_t0, ctx.trace_t1, tuple(params["prefixes"]))
    if not runs:
        return None
    return 1e3 * seconds / (runs * ctx.slice_steps)
