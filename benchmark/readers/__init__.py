"""Per-layer metric readers. Each metric of ``BENCHMARK.json``'s
``per_layer`` has a file ``benchmark/layer_metrics/<name>.json`` naming a
reader module here and its parameters; a reader is a function
``read(ctx, params)`` that returns the number, or ``None`` when it finds
nothing to read (the harness then leaves the metric out of the line).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..lib.stats import Record, slices_within
from ..lib.trace import DeviceTrace, Trace, module_runs

WINDOW_SPAN = "bench:window"


@dataclass
class Context:
    """What a reader may read. Host times (``t0``, ``t1``, ``window_t1``,
    records, slices) are ``time.monotonic`` seconds; trace times
    (``trace_t0``, ``trace_t1``, the device's spans) are the profiler's
    seconds. ``t0..t1`` and ``trace_t0..trace_t1`` bound the same traced
    part of the window; ``t0..window_t1`` is the whole measured window,
    which the counters and the consumers' log cover and the trace does not."""

    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    cell: Dict[str, Any]
    chip: Optional[Dict[str, Any]]  # the peaks row; None in --dry
    trace: Optional[Trace]
    device: Optional[DeviceTrace]  # the busiest device plane
    records: Sequence[Record]
    slices: List[Tuple[float, float, int]]  # (t_end, gap_s, rows) inside the whole window
    slice_steps: int
    compiles: int
    t0: float
    t1: float
    window_t1: float  # host time at which the whole measured window closed
    trace_t0: float
    trace_t1: float

    @classmethod
    def build(cls, *, cfg, mix, cell, chip, trace, records, slices, slice_steps,
              compiles, t0, t1, window_t1) -> "Context":
        device = None
        trace_t0 = trace_t1 = 0.0
        if trace is not None and trace.devices:
            device = max(trace.devices.values(), key=lambda d: len(d.ops))
            span = next((s for s in trace.host if s[2] == WINDOW_SPAN), None)
            if span is not None:
                trace_t0, trace_t1 = span[0], span[1]
            elif device.ops:
                trace_t0, trace_t1 = device.ops[0][0], max(b for _, b, _ in device.ops)
        return cls(cfg=cfg, mix=mix, cell=cell, chip=chip, trace=trace, device=device,
                   records=records, slices=slices_within(slices, t0, window_t1), slice_steps=slice_steps,
                   compiles=compiles, t0=t0, t1=t1, window_t1=window_t1,
                   trace_t0=trace_t0, trace_t1=trace_t1)

    def host_time(self, trace_t: float) -> float:
        """The host clock's reading at the profiler's time ``trace_t``:
        the traced part starts at ``t0`` on the one and ``trace_t0`` on
        the other."""
        return trace_t - self.trace_t0 + self.t0

    def program_runs(self, pattern: str) -> List[Tuple[float, float, str]]:
        """Runs on the device, whole inside the traced part, of the
        programs whose name matches ``pattern``, as the trace has them."""
        if self.device is None:
            return []
        return module_runs(self.device, pattern, self.trace_t0, self.trace_t1)

    def slice_work(self, t: float) -> List[Tuple[int, int]]:
        """What a decode slice that is running at host time ``t`` had to do, as
        the consumers' log has it: for every row decoding then, the tokens
        of context it held and the tokens of the slice it still needed
        (a row that retires in the slice needs fewer than ``slice_steps``;
        the steps past its end are the program's, not the algorithm's)."""
        work = []
        for r in self.records:
            if not r.events or r.events[0][0] > t:
                continue
            had = sum(n for te, n in r.events if te <= t)
            need = min(self.slice_steps, len(r.tokens) - had)
            if need > 0:
                work.append((r.prompt_tokens + had, need))
        return work
