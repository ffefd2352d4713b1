"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
readers need: device busy time, device operations by name, program
(XLA module) runs, idle gaps and what the host did in them.

Read with ``jax.profiler.ProfileData`` alone. Device planes are named
``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event per
executed operation and ``XLA Modules`` one per executed program. Host
threads are lines of the plane ``/host:CPU``.
"""

from __future__ import annotations

import gzip
import re
from bisect import bisect_right
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Span = Tuple[float, float, str]  # start s, end s, name


@dataclass
class DeviceTrace:
    ops: List[Span] = field(default_factory=list)
    modules: List[Span] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, DeviceTrace] = field(default_factory=dict)
    host: List[Span] = field(default_factory=list)


def find_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def _profile(path: Path):
    """The planes of an xplane file (plain or ``.gz``)."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def load(path: Path) -> Trace:
    """Reduce an xplane file to device and host spans."""
    data = _profile(path)
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = trace.devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _spans(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _spans(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.host.extend(s for s in _spans(line) if s[1] > s[0])
    trace.host.sort()
    return trace


def _spans(line) -> List[Span]:
    out = [
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
        for e in line.events
    ]
    out.sort()
    return out


def clip(spans: Sequence[Span], t0: float, t1: float) -> List[Span]:
    return [
        (max(a, t0), min(b, t1), n) for a, b, n in spans if b > t0 and a < t1
    ]


def union_seconds(spans: Sequence[Span]) -> float:
    """Length of the union of the spans (they may nest or overlap)."""
    total, end = 0.0, float("-inf")
    for a, b, _ in sorted(spans):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def busy_seconds(dev: "DeviceTrace", t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` in which an operation ran on the device."""
    return union_seconds(clip(dev.ops, t0, t1))


def idle_gaps(spans: Sequence[Span], t0: float, t1: float) -> List[Tuple[float, float]]:
    """The intervals of ``[t0, t1]`` that no span covers."""
    gaps, end = [], t0
    for a, b, _ in sorted(clip(spans, t0, t1)):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if t1 > end:
        gaps.append((end, t1))
    return gaps


def strip_id(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one kind of operation; an
    operation given as HLO text (``%fusion.1 = bf16[..] fusion(..)``) is
    its name alone; a program's name loses its ``(...)`` suffix."""
    if name.startswith("PjitFunction(") and name.endswith(")"):
        return "pjit:" + name[len("PjitFunction("):-1]  # the host's call of a jitted function
    name = name.split(" = ")[0].lstrip("%").split("(")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def module_of(modules: Sequence[Span], t: float) -> str:
    """Name of the program running on the device at time ``t``."""
    i = bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return strip_id(modules[i][2])
    return "?"


# operations that only wrap others (their bodies' operations are events of
# their own on the same line): counting them would count device time twice
WRAPPERS = {"while", "conditional", "call"}
NAMED_GAP_SECONDS = 1e-3  # shorter gaps are not looked up among host spans


def top_device_ops(dev: DeviceTrace, t0: float, t1: float, n: int = 10) -> List[List]:
    """The operations that took most device time in the window, as
    ``program/operation`` with operation ids stripped."""
    total: Dict[str, float] = {}
    for a, b, name in clip(dev.ops, t0, t1):
        op = strip_id(name)
        if op in WRAPPERS:
            continue
        key = f"{module_of(dev.modules, a)}/{op}"
        total[key] = total.get(key, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def top_idle_gaps(trace: Trace, dev: DeviceTrace, t0: float, t1: float, n: int = 10) -> List[List]:
    """Idle time of the device in the window by what stood around it: the
    program that ran before the gap, the one after, and for a gap of a
    millisecond or more the shortest host span that covers most of it
    (``host`` where the trace has none)."""
    long_host = [s for s in trace.host if s[1] - s[0] >= NAMED_GAP_SECONDS / 2 and s[2] != "bench:window"]
    total: Dict[str, float] = {}
    for a, b in idle_gaps(dev.ops, t0, t1):
        before = module_of(dev.modules, a - 1e-9)
        after = module_of(dev.modules, b + 1e-9)
        doing = _host_during(long_host, a, b) if b - a >= NAMED_GAP_SECONDS else "-"
        key = f"{before}>{after}|{doing}"
        total[key] = total.get(key, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def _host_during(host: Sequence[Span], a: float, b: float) -> str:
    best, best_len = "host", float("inf")
    for s, e, name in host:
        if s >= b:
            break  # sorted by start
        if min(e, b) - max(s, a) > 0.5 * (b - a) and e - s < best_len:
            best, best_len = strip_id(name), e - s
    return best


def module_runs(dev: DeviceTrace, pattern: str, t0: float, t1: float) -> List[Span]:
    """Runs, whole inside the window, of the programs whose name matches."""
    rx = re.compile(pattern)
    return [s for s in dev.modules if s[0] >= t0 and s[1] <= t1 and rx.search(s[2])]
