"""Device seconds under scope names ``lib/scopes.py`` does not know.

``scopes.SCOPES`` is the list the four ``step.*_ms_per_step`` metrics were
accepted with, and ``scopes.scope_of`` sees no other name: an operation under
``moe.experts`` reads there as unscoped (it falls into
``step.other_ms_per_step``). The readers of the expert layer and of latent
attention need those names, so this file reduces the same trace
(``scopes.load``: the busiest TPU plane's operations with their ``op_name``
paths) by an operation's INNERMOST scope among ``scopes.SCOPES`` and the
expert layer's own, and hands back the seconds of the scopes asked for.
Nothing that ``scopes.py`` computes changes.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from typing import Dict, Optional, Sequence, Tuple

from . import scopes
from .trace import WRAPPERS, find_xplane, strip_id

# the expert layer's scopes (models/transformer.py, _moe_parts)
MOE_SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.zero", "moe.combine")
KNOWN = frozenset(scopes.SCOPES + MOE_SCOPES)

_DEVICE: Dict[str, Optional[scopes.DeviceOps]] = {}


def innermost(op_name: str) -> Optional[str]:
    """The innermost known scope on an ``op_name`` path (the stat reads ``op_name:op_type``)."""
    for part in reversed(op_name.split(":")[0].split("/")):
        if part in KNOWN:
            return part
    return None


def wanted(scope: Optional[str], names: Sequence[str]) -> bool:
    """Whether ``scope`` is one of ``names``, or under a prefix ending in ``.``."""
    return scope is not None and any(scope == n or (n.endswith(".") and scope.startswith(n)) for n in names)


def reduce(dev: scopes.DeviceOps, module: str, t0: float, t1: float,
           names: Sequence[str]) -> Tuple[float, int, float]:
    """``(seconds under names, runs, seconds under any known scope)`` over the
    runs, whole inside ``[t0, t1]``, of the programs matching ``module``."""
    rx = re.compile(module)
    runs = [(a, b) for a, b, n in dev.modules if a >= t0 and b <= t1 and rx.search(strip_id(n))]
    starts = [a for a, _, _ in dev.ops]
    hit = scoped = 0.0
    for a, b in runs:
        for s, e, mid in dev.ops[bisect_right(starts, a - 1e-12):]:
            if s >= b:
                break
            name, op_name = dev.meta.get(mid, ("", ""))
            if strip_id(name) in WRAPPERS:
                continue
            scope = innermost(op_name)
            if scope is not None:
                scoped += e - s
            if wanted(scope, names):
                hit += e - s
    return hit, len(runs), scoped


def seconds_under(module: str, t0: float, t1: float, names: Sequence[str]) -> Optional[Tuple[float, int]]:
    """``(seconds, runs)`` from the trace ``run.py`` has just written (read
    once per process), or nothing where there is no trace, no run of the
    program, or no operation of it under any known scope (an older program)."""
    path = find_xplane(scopes.TRACE_DIR)
    if path is None:
        return None
    key = str(path)
    if key not in _DEVICE:
        _DEVICE.clear()
        _DEVICE[key] = scopes.load(path)
    dev = _DEVICE[key]
    if dev is None:
        return None
    hit, runs, scoped = reduce(dev, module, t0, t1, names)
    if not runs or not scoped:
        return None
    return hit, runs
