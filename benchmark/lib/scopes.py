"""From a device operation in a profiler trace to the ``jax.named_scope``
the program put it under.

``jax.profiler.ProfileData`` (what ``lib/trace.py`` reads with) gives an
event's own stats only: offset and duration. The scope is in the event's
METADATA: each ``XLA Ops`` event of a TPU plane points at an
``XEventMetadata`` whose stat ``tf_op`` holds the operation's ``op_name`` as
JAX wrote it into the HLO, a ``/``-joined path that carries every enclosing
scope: ``jit(decode)/while/body/while/body/attn.core/dot_general`` (looked at
by hand in a trace of the chip, PERF.md §3; an operation the compiler made
itself, such as a copy around a loop's carry, has none). So this file reads
the ``.xplane.pb`` a second time, as protobuf wire format, and takes only
what it needs: the busiest TPU plane's ``XLA Modules`` and ``XLA Ops`` lines
and the metadata tables. No schema is installed with JAX; the field numbers
below are those of ``xplane.proto`` (XSpace.planes=1; XPlane.name=2, lines=3,
event_metadata=4, stat_metadata=5; XLine.name=2, timestamp_ns=3, events=4;
XEvent.metadata_id=1, offset_ps=2, duration_ps=3; XEventMetadata.id=1,
name=2, stats=5; XStat.metadata_id=1, str_value=5, ref_value=7;
XStatMetadata.id=1, name=2).

``run.py`` leaves the trace in ``benchmark/.out/trace`` until the readers
have run; it is read once per process.
"""

from __future__ import annotations

import gzip
import re
from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from .trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, WRAPPERS, find_xplane, strip_id

TRACE_DIR = Path(__file__).resolve().parents[1] / ".out" / "trace"
OP_NAME_STAT = "tf_op"
# the program's scope names (models/transformer.py, engine/jax_engine.py, ops/sampling.py)
SCOPES = ("embed", "attn.norm_qkv", "attn.kv_write", "attn.kv_gather", "attn.core", "attn.out",
          "mlp", "head", "sample", "carry")


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one protobuf message: an int
    for varints and fixed-width fields, a memoryview for length-delimited
    ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield no, kind, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


@dataclass
class DeviceOps:
    """One TPU plane: program runs and operations on the profiler's clock
    (seconds), each operation with its name and its ``op_name`` path."""

    modules: List[Tuple[float, float, str]] = field(default_factory=list)
    ops: List[Tuple[float, float, int]] = field(default_factory=list)  # start, end, metadata id
    meta: Dict[int, Tuple[str, str]] = field(default_factory=dict)  # id -> (name, op_name or "")


def _map_entry(view) -> Tuple[int, object]:
    key, value = 0, b""
    for no, _, v in fields(view):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(view) -> Tuple[str, DeviceOps]:
    name = ""
    lines, event_meta, stat_names = [], [], {}
    for no, _, v in fields(view):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            event_meta.append(v)
        elif no == 5:
            sid, body = _map_entry(v)
            for n2, _, v2 in fields(body):
                if n2 == 2:
                    stat_names[sid] = _text(v2)
    dev = DeviceOps()
    if not DEVICE_PLANE.match(name):
        return name, dev
    op_name_ids = {sid for sid, n in stat_names.items() if n == OP_NAME_STAT}
    refs: Dict[int, int] = {}  # metadata id -> stat metadata id holding the string (ref_value)
    for entry in event_meta:
        mid, body = _map_entry(entry)
        ev_name, op_name = "", ""
        for n2, _, v2 in fields(body):
            if n2 == 2:
                ev_name = _text(v2)
            elif n2 == 5:
                sid, text, ref = 0, None, None
                for n3, _, v3 in fields(v2):
                    if n3 == 1:
                        sid = v3
                    elif n3 == 5:
                        text = v3
                    elif n3 == 7:
                        ref = v3
                if sid in op_name_ids:
                    if text is not None:
                        op_name = _text(text)
                    elif ref is not None:
                        refs[mid] = ref
        dev.meta[mid] = (ev_name, op_name)
    for mid, ref in refs.items():  # a string kept once, as a stat metadata's name
        dev.meta[mid] = (dev.meta[mid][0], stat_names.get(ref, ""))
    for line in lines:
        line_name, t_line, events = "", 0, []
        for no, _, v in fields(line):
            if no == 2:
                line_name = _text(v)
            elif no == 3:
                t_line = v
            elif no == 4:
                events.append(v)
        if line_name not in (OPS_LINE, MODULES_LINE):
            continue
        spans = []
        for ev in events:
            mid = offset = dur = 0
            for no, _, v in fields(ev):
                if no == 1:
                    mid = v
                elif no == 2:
                    offset = v
                elif no == 3:
                    dur = v
            start = t_line * 1e-9 + offset * 1e-12
            spans.append((start, start + dur * 1e-12, mid))
        spans.sort()
        if line_name == OPS_LINE:
            dev.ops = spans
        else:
            dev.modules = [(a, b, dev.meta.get(m, ("", ""))[0]) for a, b, m in spans]
    return name, dev


def load(path: Path) -> Optional[DeviceOps]:
    """The busiest TPU plane of an xplane file (plain or ``.gz``)."""
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    best = None
    for no, kind, v in fields(raw):
        if no == 1 and kind == 2:
            _, dev = _plane(v)
            if dev.ops and (best is None or len(dev.ops) > len(best.ops)):
                best = dev
    return best


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of the program's scopes on an ``op_name`` path."""
    for part in reversed(op_name.split(":")[0].split("/")):  # the stat reads "op_name:op_type"
        if part in SCOPES:
            return part
    return None


@dataclass
class ScopeTable:
    runs: int = 0
    total_seconds: float = 0.0
    scoped_seconds: float = 0.0  # under any of SCOPES
    by_scope: Dict[Optional[str], float] = field(default_factory=dict)
    unscoped_by_kind: Dict[str, float] = field(default_factory=dict)  # operation kind -> seconds


def reduce(dev: DeviceOps, module: str, t0: float, t1: float) -> ScopeTable:
    """Device seconds by scope over the runs, whole inside ``[t0, t1]``, of
    the programs whose name matches ``module``. Operations that only wrap
    others (``while``, ``call``: their bodies are events of their own) are
    left out, as in ``trace.top_device_ops``."""
    rx = re.compile(module)
    runs = [(a, b) for a, b, n in dev.modules if a >= t0 and b <= t1 and rx.search(strip_id(n))]
    table = ScopeTable(runs=len(runs))
    starts = [a for a, _, _ in dev.ops]
    for a, b in runs:
        for s, e, mid in dev.ops[bisect_right(starts, a - 1e-12):]:
            if s >= b:
                break
            name, op_name = dev.meta.get(mid, ("", ""))
            kind = strip_id(name)
            if kind in WRAPPERS:
                continue
            scope = scope_of(op_name)
            table.total_seconds += e - s
            table.by_scope[scope] = table.by_scope.get(scope, 0.0) + (e - s)
            if scope is None:
                table.unscoped_by_kind[kind] = table.unscoped_by_kind.get(kind, 0.0) + (e - s)
            else:
                table.scoped_seconds += e - s
    return table


_LOADED: Dict[str, Optional[DeviceOps]] = {}
_TABLES: Dict[Tuple[str, str, float, float], ScopeTable] = {}


def for_window(module: str, t0: float, t1: float) -> Optional[ScopeTable]:
    """The table of the trace ``run.py`` has just written (read once per
    process), or nothing where there is no trace."""
    path = find_xplane(TRACE_DIR)
    if path is None:
        return None
    key = str(path)
    if key not in _LOADED:
        _LOADED.clear()
        _TABLES.clear()
        _LOADED[key] = load(path)
    dev = _LOADED[key]
    if dev is None:
        return None
    tkey = (key, module, t0, t1)
    if tkey not in _TABLES:
        _TABLES[tkey] = reduce(dev, module, t0, t1)
    return _TABLES[tkey]
