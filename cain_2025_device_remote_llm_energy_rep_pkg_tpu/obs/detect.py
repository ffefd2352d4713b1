"""Streaming anomaly detection and goodput accounting.

Three detectors, all stdlib-only and honoring the shared kill switch:

- **Per-cell run CV** (:class:`CellCvTracker`). The paper's headline
  claims rest on run-to-run stability (30 repetitions per cell; ROADMAP
  #1 demands ≤5% CV on the re-run capstone) — but CV was only computed
  post-hoc by the analysis pipeline. A Welford rolling mean/variance
  per (model, length, location) cell over each run's modelled Joules
  and wall time makes the target observable *during* a study:
  ``llm_run_cell_cv{metric,model,length,location}`` gauges update per
  run, and a cell whose CV breaches the threshold after enough
  repetitions fires an anomaly event (once per cell per breach episode
  — re-arming only after the CV recovers — so a noisy cell cannot
  flood the ring). Wired in ``experiments/llm_energy.py``'s
  ``populate_run_data``.

- **Long passes of the serving loop** (:class:`SpikeDetector`). A pass
  of the continuous scheduler's loop (reap, slice, egress, join, admit,
  sweep: one ``sched.iter``) that runs long against the rolling median
  of its predecessors is exactly the "why did this cell's CV blow up"
  moment — a collection, a recompile, a host that did not run us. The
  detector keeps a bounded window of recent passes (long ones left out)
  and of each phase's seconds, and for a long pass names the CAUSE from
  the host's own account of it (``obs/stall.py::HostSample`` deltas, the
  seconds the session waited for the device, each phase against its own
  median: :func:`classify`). It fires ONE ``pass_stall`` anomaly with the
  deltas, the phase, the cause and the last few flight-recorder events
  as an exemplar, a ``stall`` span under the pass, and
  ``llm_sched_stall_seconds_total{cause}``. One a scheduler, fed by its
  loop for every pass that ran a slice. A slice the session saw compile
  (sessions compile their step at open, so this is a mid-session
  recompile stalling resident rows) additionally fires its own
  ``compile_in_slice`` anomaly (:func:`observe_slice_compile`).

- **Goodput accounting** (``observe_slice_tokens`` /
  ``observe_retired_tokens``). A stepped decode slice steps EVERY row
  of the batch bucket — live rows, rows that finished mid-slice, and
  padding rows alike. ``llm_engine_goodput_tokens_total`` counts
  tokens on rows that actually completed; ``llm_engine_stepped_tokens_
  total`` counts every (row × step) the device executed. Their ratio
  is the wasted-step fraction the continuous scheduler exists to
  minimize — the number that shows whether iteration-level retirement
  is actually paying for its host round-trips.
"""

from __future__ import annotations

import os
import statistics
import threading
from collections import deque
from typing import Any, Dict, Mapping, Optional, Tuple

from .flight import EV_ANOMALY, FLIGHT
from .metrics import REGISTRY, enabled
from .trace import TRACER

# ROADMAP #1's stability target: flag cells whose run-to-run CV exceeds
# this once enough repetitions exist to estimate it.
CELL_CV_THRESHOLD = float(os.environ.get("TPU_LLM_CV_THRESHOLD", 0.05))
CELL_CV_MIN_RUNS = int(os.environ.get("TPU_LLM_CV_MIN_RUNS", 3))
# A pass is long when it exceeds the rolling median by more than the
# larger of these: seconds (above every cell's join, 23-37 ms: PERF.md §5)
# and a share of the median.
STALL_EXCESS_S = 0.05
STALL_EXCESS_SHARE = 0.15
SPIKE_MIN_SAMPLES = 8
SPIKE_WINDOW = 64
# Flight events attached to a spike anomaly as the exemplar context.
SPIKE_EXEMPLAR_EVENTS = 8

CELL_CV_G = REGISTRY.gauge(
    "llm_run_cell_cv",
    "Run-to-run coefficient of variation of one study cell, by metric "
    "(energy_J: modelled Joules; wall_s: request wall time). ROADMAP #1 "
    "targets <= 0.05",
    labels=("metric", "model", "length", "location"),
)
CELL_RUNS_G = REGISTRY.gauge(
    "llm_run_cell_runs",
    "Repetitions observed so far for one study cell",
    labels=("model", "length", "location"),
)
ANOMALY_C = REGISTRY.counter(
    "llm_anomaly_total",
    "Anomalies fired by the streaming detectors, by kind "
    "(cell_cv: a study cell's run-to-run CV breached the threshold; "
    "pass_stall: a pass of the serving loop ran long against the rolling "
    "median; "
    "compile_in_slice: a decode slice compiled while rows were resident)",
    labels=("kind",),
)
STALL_SECONDS_C = REGISTRY.counter(
    "llm_sched_stall_seconds_total",
    "Seconds by which passes of the continuous scheduler's loop ran over "
    "their rolling median, by the cause the host's account names "
    "(process, compile, gc, throttled, run_queue, page_fault, device_wait, "
    "host:<phase>, unknown)",
    labels=("cause",),
)
GOODPUT_C = REGISTRY.counter(
    "llm_engine_goodput_tokens_total",
    "Generated tokens on rows that COMPLETED (retired eos/budget) — the "
    "numerator of the stepped decode path's goodput fraction",
)
STEPPED_C = REGISTRY.counter(
    "llm_engine_stepped_tokens_total",
    "Row-steps the stepped decode path executed (every batch-bucket row "
    "of every step: live, done-but-not-retired and padding rows alike) "
    "— the denominator of the goodput fraction",
)


def observe_slice_tokens(steps: int, bucket_rows: int) -> None:
    """Bill one decode slice's device work: ``steps`` loop iterations ran
    and each stepped all ``bucket_rows`` rows of the batch bucket."""
    if steps > 0 and bucket_rows > 0:
        STEPPED_C.inc(steps * bucket_rows)


def observe_retired_tokens(generated_tokens: int) -> None:
    """Credit a COMPLETED row's tokens as goodput (error/shutdown rows
    never credit — their tokens were wasted work by definition)."""
    if generated_tokens > 0:
        GOODPUT_C.inc(generated_tokens)


class Welford:
    """Streaming mean/variance (Welford 1962): one pass, O(1) state."""

    __slots__ = ("count", "mean", "_m2")

    def __init__(self) -> None:
        self.count = 0
        self.mean = 0.0
        self._m2 = 0.0

    def update(self, x: float) -> None:
        self.count += 1
        delta = x - self.mean
        self.mean += delta / self.count
        self._m2 += delta * (x - self.mean)

    @property
    def variance(self) -> float:
        """Sample variance (n-1 denominator); 0 before two observations."""
        return self._m2 / (self.count - 1) if self.count > 1 else 0.0

    @property
    def std(self) -> float:
        return self.variance**0.5

    @property
    def cv(self) -> Optional[float]:
        """Coefficient of variation; None until two runs or at zero mean."""
        if self.count < 2 or self.mean == 0.0:
            return None
        return abs(self.std / self.mean)


class CellCvTracker:
    """Welford rolling CV per (model, length, location) study cell (see
    the module docstring). ``observe_run`` is the one entry point."""

    def __init__(
        self,
        threshold: float = CELL_CV_THRESHOLD,
        min_runs: int = CELL_CV_MIN_RUNS,
    ) -> None:
        self.threshold = threshold
        self.min_runs = min_runs
        self._lock = threading.Lock()
        # (metric, model, length, location) -> Welford
        self._cells: Dict[Tuple[str, str, str, str], Welford] = {}
        # cells currently in breach (re-arm only after recovery)
        self._breached: set = set()

    def observe_run(
        self,
        model: str,
        length,
        location: str,
        energy_J: Optional[float] = None,
        wall_s: Optional[float] = None,
    ) -> Dict[str, Optional[float]]:
        """Fold one run into its cell; returns {metric: cv} (values may
        be None while the cell has < 2 runs). No-op when telemetry is
        off."""
        out: Dict[str, Optional[float]] = {}
        if not enabled():
            return out
        model, length, location = str(model), str(length), str(location)
        samples = (("energy_J", energy_J), ("wall_s", wall_s))
        with self._lock:
            for metric, value in samples:
                if value is None:
                    continue
                key = (metric, model, length, location)
                cell = self._cells.get(key)
                if cell is None:
                    cell = self._cells[key] = Welford()
                cell.update(float(value))
                out[metric] = cell.cv
                if metric == "energy_J":
                    CELL_RUNS_G.labels(
                        model=model, length=length, location=location
                    ).set(cell.count)
                if cell.cv is None:
                    continue
                CELL_CV_G.labels(
                    metric=metric,
                    model=model,
                    length=length,
                    location=location,
                ).set(round(cell.cv, 6))
                if cell.count < self.min_runs:
                    continue
                if cell.cv > self.threshold:
                    if key not in self._breached:
                        self._breached.add(key)
                        self._fire_cell(key, cell)
                else:
                    self._breached.discard(key)
        return out

    def _fire_cell(
        self, key: Tuple[str, str, str, str], cell: Welford
    ) -> None:
        metric, model, length, location = key
        ANOMALY_C.labels(kind="cell_cv").inc()
        FLIGHT.emit(
            EV_ANOMALY,
            kind="cell_cv",
            metric=metric,
            model=model,
            length=length,
            location=location,
            cv=round(cell.cv or 0.0, 6),
            threshold=self.threshold,
            runs=cell.count,
            mean=round(cell.mean, 6),
        )

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able state of every tracked cell (the /debug/state and
        mid-study introspection surface)."""
        with self._lock:
            return {
                "|".join(key): {
                    "runs": cell.count,
                    "mean": round(cell.mean, 6),
                    "cv": round(cell.cv, 6) if cell.cv is not None else None,
                    "breached": key in self._breached,
                }
                for key, cell in self._cells.items()
            }

    def reset(self) -> None:
        """Test isolation only."""
        with self._lock:
            self._cells.clear()
            self._breached.clear()


# The phases of a pass the rule can name (``host:<phase>``), in the
# loop's order; ``slice`` is the session's host side of the slice
# (dispatch, fetch, account), ``wait`` its wait for the device and ``cpu``
# the loop thread's own CPU time: each is judged against its own median.
HOST_PHASES = ("reap", "slice", "egress", "join", "admit", "sweep")
_TRACKED = HOST_PHASES + ("wait", "cpu")


def classify(
    excess_s: float,
    deltas: Mapping[str, Any],
    phase_excess: Mapping[str, float],
) -> str:
    """Why a pass ran ``excess_s`` over the median: the FIRST of these
    that explains at least half of the excess. ``deltas`` are the pass's
    ``HostSample.since``; ``phase_excess`` each phase's seconds over its
    own rolling median. The order is from what silences the others
    downward: when the whole process stood still (``process``) the loop
    thread's own run-queue time says nothing, since it was waiting for
    the device of its own will; a compile, a collection, a throttled
    cgroup and a busy run queue each show in their own counter;
    ``device_wait`` means the excess lies in the session's wait for the
    device, so the host was the one waiting; ``host:<phase>`` that the
    thread's own CPU time rose, in the phase named. A pass none of these
    explains is ``unknown``; its ``phase`` still says where it sat."""
    half = 0.5 * excess_s

    def d(key: str) -> float:
        return deltas.get(key) or 0

    cpu_rose = phase_excess.get("cpu", 0.0) >= half
    if d("process_stall_s") >= half:
        return "process"
    if d("compiles") > 0:
        return "compile"
    if d("gc_s") >= half:
        return "gc"
    if d("throttled_s") >= half:
        return "throttled"
    if d("run_delay_s") >= half:
        return "run_queue"
    if d("majflt") > 0 and not cpu_rose:
        return "page_fault"
    if phase_excess.get("wait", 0.0) >= half:
        return "device_wait"
    if cpu_rose:
        return "host:" + max(HOST_PHASES, key=lambda p: phase_excess.get(p, 0.0))
    return "unknown"


class SpikeDetector:
    """Long passes against a rolling median, and their cause (see the
    module docstring). One instance per monitored stream."""

    def __init__(
        self,
        name: str = "sched_pass",
        min_samples: int = SPIKE_MIN_SAMPLES,
        window: int = SPIKE_WINDOW,
    ) -> None:
        self.name = name
        self.min_samples = min_samples
        self._lock = threading.Lock()
        self._window: "deque[float]" = deque(maxlen=window)
        # each windowed pass's seconds by :data:`_TRACKED`, one tuple a pass
        self._phases: "deque[Tuple[float, ...]]" = deque(maxlen=window)
        self.passes = 0
        self.count = 0
        self.seconds = 0.0
        self.by_cause: Dict[str, float] = {}
        self.last: Optional[Dict[str, Any]] = None

    def observe(
        self,
        dur_s: float,
        trace: Optional[int] = None,
        deltas: Optional[Mapping[str, Any]] = None,
        phases: Optional[Mapping[str, float]] = None,
        t0_s: Optional[float] = None,
    ) -> bool:
        """Fold one pass in; returns True (and fires the anomaly, the
        ``stall`` span and the counter) when it is long against the PRIOR
        window. Long passes are excluded from the windows so one outlier
        cannot drag the median up and mask its successors. ``phases``
        holds the pass's seconds by phase (:data:`HOST_PHASES` and
        ``wait``); ``t0_s`` its start on ``time.monotonic``, for the span.
        No-op when telemetry is off."""
        if not enabled():
            return False
        deltas = deltas or {}
        seen = dict(phases or {}, cpu=deltas.get("thread_cpu_s") or 0.0)
        row = tuple(seen.get(key, 0.0) for key in _TRACKED)
        with self._lock:
            self.passes += 1
            long_pass = False
            median = 0.0
            if len(self._window) >= self.min_samples:
                median = statistics.median(self._window)
                long_pass = median > 0 and dur_s - median > max(
                    STALL_EXCESS_S, STALL_EXCESS_SHARE * median
                )
            if not long_pass:
                self._window.append(dur_s)
                self._phases.append(row)
                return False
            excess_s = dur_s - median
            phase_excess = {
                key: max(0.0, mine - statistics.median(theirs))
                for key, mine, theirs in zip(_TRACKED, row, zip(*self._phases))
            }
            cause = classify(excess_s, deltas, phase_excess)
            phase = max(
                HOST_PHASES + ("wait",), key=lambda p: phase_excess[p]
            )
            attrs = {
                "cause": cause,
                "excess_s": round(excess_s, 6),
                "phase": phase if phase_excess[phase] > 0 else None,
                "dur_s": round(dur_s, 6),
                "median_s": round(median, 6),
                "wait_excess_s": round(phase_excess["wait"], 6),
                "cpu_excess_s": round(phase_excess["cpu"], 6),
                **deltas,
            }
            self.count += 1
            self.seconds += excess_s
            self.by_cause[cause] = self.by_cause.get(cause, 0.0) + excess_s
            self.last = attrs
        ANOMALY_C.labels(kind="pass_stall").inc()
        STALL_SECONDS_C.labels(cause=cause).inc(excess_s)
        if t0_s is not None:
            TRACER.add_span("stall", t0_s, t0_s + dur_s, attrs)
        # the exemplar: what the recorder saw just before the stall —
        # the joins/slices/retirements the histogram cannot name
        exemplar = [
            {"seq": e["seq"], "type": e["type"], "trace": e.get("trace")}
            for e in FLIGHT.events(n=SPIKE_EXEMPLAR_EVENTS)
        ]
        FLIGHT.emit(
            EV_ANOMALY,
            trace=trace,
            kind="pass_stall",
            stream=self.name,
            exemplar=exemplar,
            **attrs,
        )
        return True

    def snapshot(self) -> Dict[str, Any]:
        """The ``stalls`` block of ``/debug/state``."""
        with self._lock:
            return {
                "passes": self.passes,
                "count": self.count,
                "seconds": round(self.seconds, 6),
                "by_cause": {
                    k: round(v, 6) for k, v in self.by_cause.items()
                },
                "last": self.last,
            }

    def reset(self) -> None:
        """A new session: its period is another, the totals stay."""
        with self._lock:
            self._window.clear()
            self._phases.clear()


def observe_slice_compile(dur_s: float, trace: Optional[int] = None) -> None:
    """A decode slice compiled (or loaded an executable from the
    persistent cache) with rows resident: fire a ``compile_in_slice``
    anomaly. The pass still goes through the scheduler's detector like
    any other. No-op when telemetry is off."""
    if not enabled():
        return
    ANOMALY_C.labels(kind="compile_in_slice").inc()
    FLIGHT.emit(
        EV_ANOMALY,
        trace=trace,
        kind="compile_in_slice",
        stream="decode_slice",
        dur_s=round(dur_s, 6),
    )


# Process-wide: the study's cell tracker (the serving path's detector of
# long passes belongs to its scheduler).
CELL_CV = CellCvTracker()
