"""Why a pass of the serving loop ran long: the host's own account.

Three pieces, all stdlib, all behind ``obs.metrics.enabled()`` (off means
no callback installed, no thread started, no ``/proc`` file opened):

- :class:`HostSample`: what the operating system and the collector say
  about the CALLING thread at one instant: its CPU time, the time it sat
  runnable and waiting for a CPU (``/proc/thread-self/schedstat``), its
  context switches and major faults (``getrusage(RUSAGE_THREAD)``), the
  cgroup's throttled time, the collector's cumulated pause. The
  scheduler's loop takes one as each ``sched.iter`` closes; the deltas
  between two are that pass's, and ``obs/detect.py::classify`` names a
  long pass's cause from them.
- :class:`GcWatch`: one ``gc.callbacks`` entry. Every collection is
  summed (two clock reads); one of generation 2 runs inside a profiler
  annotation ``gc``, so it lies on the profiler's host plane beside the
  device's operations; that one, and a younger one that took a
  millisecond or more, becomes a ``gc`` span of the ring at the next host
  sample. The callback runs on whichever thread allocated, also one that
  holds the tracer's lock: it takes no lock itself.
- :class:`Heartbeat`: a thread that sleeps :data:`TICK_S` and records an
  overshoot of :data:`STALL_S` or more as a ``stall.process`` span: the
  whole process, not the loop, stood still. ``held_by`` says which of
  two things it was, from a witness that needs no second look at the
  interpreter: a thread that waits for the interpreter lock wakes every
  switch interval (5 ms) to ask for it, so the heartbeat's own voluntary
  context switches (``getrusage``) count its waits. Many of them: a
  thread of ours held the lock, and the heartbeat, which takes the lock
  the moment the holder drops it, reads every thread's innermost frames
  (``sys._current_frames``) while the holder still stands where it held.
  None, and no CPU time spent by the process: ``process not scheduled``,
  the machine did not run us. (``faulthandler.dump_traceback_later``, a C
  thread that walks the other threads' frames WITHOUT the lock, would see
  the holder mid-stall too, but it crashes the process when a thread runs
  Python while it walks: a dump every 2 ms against three busy threads
  segfaults within seconds, and one run in four of a rehearsal of the
  benchmark did. A witness must not be able to kill the server.)

A field the platform lacks is ``None``; nothing here raises into the loop.
"""

from __future__ import annotations

import gc
import re
import resource
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from .metrics import enabled
from .trace import TRACER, _annotation

SCHEDSTAT_PATH = "/proc/thread-self/schedstat"
# the cgroup's own cpu.stat as a container sees it: v2 (`throttled_usec`),
# then v1 (`throttled_time`, ns). The first that parses is kept.
CPU_STAT_PATHS = (
    "/sys/fs/cgroup/cpu.stat",
    "/sys/fs/cgroup/cpu/cpu.stat",
    "/sys/fs/cgroup/cpu,cpuacct/cpu.stat",
)
PRESSURE_PATHS = {
    "cpu": "/proc/pressure/cpu",
    "memory": "/proc/pressure/memory",
    "io": "/proc/pressure/io",
}
PROC_STAT_PATH = "/proc/stat"

TICK_S = 0.05  # the heartbeat's sleep
STALL_S = 0.1  # an overshoot of the sleep worth a `stall.process` span
PSI_EVERY_S = 1.0  # the pressure files' baseline is at most this old
# a thread that waits for the interpreter lock asks again every switch
# interval: at least this share of stall / interval voluntary switches
# says the heartbeat spent the stall waiting for the lock
LOCK_WAIT_SHARE = 0.25
GC_SPAN_S = 1e-3  # a younger collection this long reaches the ring
GC_PENDING = 256  # collections kept for the ring while nobody samples
HELD_FRAMES = 3  # innermost frames kept of each thread in `held_by`
NOT_SCHEDULED = "process not scheduled"
HEARTBEAT_LATE = "heartbeat not scheduled"

# what a pass's deltas are called on its `sched.iter` span
DELTA_NAMES = (
    "cpu_s", "thread_cpu_s", "run_delay_s", "throttled_s",
    "gc_s", "gc_n", "nivcsw", "majflt",
)


_READ_BYTES = 4096  # every file read here says what is wanted in its head


def _read_text(path: str) -> Optional[str]:
    """The head of a small file, or None where it cannot be read: the
    one place this module opens a path."""
    try:
        with open(path) as f:
            return f.read(_READ_BYTES)
    except OSError:
        return None


_schedstat_there = True


def _schedstat() -> Tuple[Optional[int], Optional[int]]:
    """(ns on a CPU, ns runnable and waiting for one) of the calling
    thread. A kernel without the file is not asked twice."""
    global _schedstat_there
    if not _schedstat_there:
        return None, None
    text = _read_text(SCHEDSTAT_PATH)
    try:
        on_cpu, waiting = text.split()[:2]
        return int(on_cpu), int(waiting)
    except (AttributeError, ValueError):
        _schedstat_there = text is not None  # unreadable: stop asking
        return None, None


_cpu_stat_path: Optional[str] = CPU_STAT_PATHS[0]


def _throttled_s() -> Optional[float]:
    """Seconds the cgroup's tasks were throttled so far (None: no
    readable ``cpu.stat`` names it). Remembers the path that answered."""
    global _cpu_stat_path
    if _cpu_stat_path is None:
        return None
    tried = (_cpu_stat_path,) + tuple(
        p for p in CPU_STAT_PATHS if p != _cpu_stat_path
    )
    for path in tried:
        m = re.search(
            r"^throttled_(usec|time) (\d+)$", _read_text(path) or "", re.M
        )
        if m:
            _cpu_stat_path = path
            return int(m.group(2)) * (1e-6 if m.group(1) == "usec" else 1e-9)
    _cpu_stat_path = None  # none answers here: stop asking
    return None


class GcWatch:
    """The collector's pauses, summed and (the long ones) as spans."""

    def __init__(self) -> None:
        # (seconds, collections, start of the one running now): ONE tuple,
        # swapped whole, because the callback is Python code and another
        # thread may read between any two of its statements
        self._sum: Tuple[float, int, Optional[float]] = (0.0, 0, None)
        # the entered annotation of a generation-2 collection running now
        self._ann = None
        # (t0, t1, attrs, the span it ran under) of collections worth a
        # span, until :meth:`flush` hands them to the ring
        self._done: "deque[tuple]" = deque(maxlen=GC_PENDING)

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def uninstall(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def read(self, now: float) -> Tuple[float, int]:
        """(pause seconds, collections) so far; a collection that runs
        right now counts up to ``now``."""
        total_s, total_n, t0 = self._sum
        return (total_s if t0 is None else total_s + max(0.0, now - t0)), total_n

    def flush(self) -> None:
        """The finished collections into the ring as ``gc`` spans. Called
        at a host sample (the loop's pass boundary, the heartbeat's
        tick), never from the callback."""
        while self._done:
            try:
                t0, t1, attrs, parent = self._done.popleft()
            except IndexError:  # another thread's flush took the last
                return
            TRACER.add_span("gc", t0, t1, attrs, parent=parent)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # NO lock in here (module docstring): the ring's lock may be held
        # by this very thread, and it is not reentrant
        total_s, total_n, t0 = self._sum
        if phase == "start":
            self._sum = (total_s, total_n, time.monotonic())
            if info.get("generation") == 2:
                self._ann = _annotation("gc", {"generation": 2})
            return
        if t0 is None:
            return
        t1 = time.monotonic()
        self._sum = (total_s + t1 - t0, total_n + 1, None)
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        generation = info.get("generation")
        if generation == 2 or t1 - t0 >= GC_SPAN_S:
            attrs = {"generation": generation, "collected": info.get("collected")}
            self._done.append((t0, t1, attrs, TRACER.current()))


GC_WATCH = GcWatch()


class HostSample(NamedTuple):
    """The calling thread's account at one instant (module docstring)."""

    t: float  # time.monotonic
    cpu_s: float  # the process's CPU time, every thread
    thread_cpu_s: float
    nivcsw: Optional[int]  # switched out against its will
    nvcsw: Optional[int]  # switched out waiting
    majflt: Optional[int]
    on_cpu_ns: Optional[int]
    run_delay_ns: Optional[int]  # runnable, waiting for a CPU
    throttled_s: Optional[float]
    gc_s: float
    gc_n: int
    process_stall_s: float  # the heartbeat's `stall.process` seconds
    compiles: Optional[int]  # backend compiles (None: no jax here)

    @classmethod
    def take(cls) -> "HostSample":
        try:
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            nivcsw, nvcsw, majflt = ru.ru_nivcsw, ru.ru_nvcsw, ru.ru_majflt
            thread_cpu_s = ru.ru_utime + ru.ru_stime  # one call fewer
        except (AttributeError, OSError, ValueError):
            nivcsw = nvcsw = majflt = None
            thread_cpu_s = time.thread_time()
        on_cpu, waiting = _schedstat()
        now = time.monotonic()
        gc_s, gc_n = GC_WATCH.read(now)
        GC_WATCH.flush()
        return cls(
            now, time.process_time(), thread_cpu_s,
            nivcsw, nvcsw, majflt, on_cpu, waiting, _throttled_s(),
            gc_s, gc_n, HEARTBEAT.stalled_s(now), _compiles(),
        )

    def since(self, prev: "HostSample") -> Dict[str, Any]:
        """This sample less ``prev``: :data:`DELTA_NAMES`, and
        ``process_stall_s`` and ``compiles`` for the rule. A field either
        side lacks stays None."""
        run_delay_ns = _less(self.run_delay_ns, prev.run_delay_ns)
        return {
            "cpu_s": self.cpu_s - prev.cpu_s,
            "thread_cpu_s": self.thread_cpu_s - prev.thread_cpu_s,
            "run_delay_s": None if run_delay_ns is None else run_delay_ns * 1e-9,
            "throttled_s": _less(self.throttled_s, prev.throttled_s),
            "gc_s": self.gc_s - prev.gc_s,
            "gc_n": self.gc_n - prev.gc_n,
            "nivcsw": _less(self.nivcsw, prev.nivcsw),
            "majflt": _less(self.majflt, prev.majflt),
            "process_stall_s": max(0.0, self.process_stall_s - prev.process_stall_s),
            "compiles": _less(self.compiles, prev.compiles),
        }


def _less(a, b):
    """``a - b``, or None where either side is lacking."""
    return None if a is None or b is None else a - b


def _compiles() -> Optional[int]:
    """The process's backend compiles so far, where it has imported jax
    (the fake-engine server stays free of it)."""
    if "jax" not in sys.modules:
        return None
    try:
        from ..utils.compile_cache import compile_count

        return compile_count()
    except Exception:  # noqa: BLE001 — a half-imported jax
        return None


def _pressure_us() -> Dict[str, Optional[int]]:
    """``some total`` (us stalled) of each ``/proc/pressure`` file, and
    ``/proc/stat``'s steal ticks."""
    out: Dict[str, Optional[int]] = {}
    for name, path in PRESSURE_PATHS.items():
        m = re.search(r"^some .*total=(\d+)", _read_text(path) or "", re.M)
        out[f"psi_{name}_us"] = int(m.group(1)) if m else None
    first = (_read_text(PROC_STAT_PATH) or "").split("\n", 1)[0].split()
    try:
        out["steal_ticks"] = int(first[8]) if first[0] == "cpu" else None
    except (IndexError, ValueError):
        out["steal_ticks"] = None
    return out


def thread_stacks(skip_ident: Optional[int] = None) -> List[str]:
    """Where every thread of the process stands, one line a distinct
    stack: ``[n x] thread: inner < caller < caller`` with the innermost
    :data:`HELD_FRAMES` frames as ``file:line fn``. Threads that stand at
    the same place (a pool's idle workers) fold into one line."""
    names = {t.ident: t.name for t in threading.enumerate()}
    folded: Dict[str, List[str]] = {}
    for ident, frame in sys._current_frames().items():
        if ident == skip_ident:
            continue
        frames = []
        while frame is not None and len(frames) < HELD_FRAMES:
            code = frame.f_code
            frames.append(
                f"{code.co_filename.rsplit('/', 1)[-1]}:{frame.f_lineno} "
                f"{code.co_name}"
            )
            frame = frame.f_back
        folded.setdefault(" < ".join(frames), []).append(
            names.get(ident, hex(ident))
        )
    return [
        (f"{len(who)} x " if len(who) > 1 else "") + f"{who[0]}: {where}"
        for where, who in folded.items()
    ]


class Heartbeat:
    """The process's stall watch (module docstring). One a process:
    schedulers :meth:`acquire` it at start and :meth:`release` it at stop."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._users = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._total_s = 0.0  # stalled seconds less the collector's inside
        self._last_tick: "Optional[HostSample]" = None
        self._first: "Optional[HostSample]" = None  # as the thread started
        self.count = 0
        self.last: Optional[Dict[str, Any]] = None

    def acquire(self) -> None:
        with self._lock:
            self._users += 1
            if self._thread is None:
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, args=(self._stop,),
                    name="stall-heartbeat", daemon=True,
                )
                self._thread.start()

    def release(self) -> None:
        with self._lock:
            self._users = max(0, self._users - 1)
            if self._users or self._thread is None:
                return
            thread, self._thread = self._thread, None
            self._stop.set()
        thread.join(timeout=5.0)

    def stalled_s(self, now: float) -> float:
        """Seconds of ``stall.process`` so far less the collector's inside
        them, with the overshoot of a tick that is late RIGHT NOW (the
        loop may close its pass before the heartbeat has recorded the
        stall both slept through)."""
        tick = self._last_tick
        if tick is None:
            return self._total_s
        late = now - tick.t - TICK_S
        if late < STALL_S:
            return self._total_s
        return self._total_s + max(0.0, late - (GC_WATCH.read(now)[0] - tick.gc_s))

    def _run(self, stop: threading.Event) -> None:
        ident = threading.get_ident()
        prev = self._first = HostSample.take()
        # the pressure files cost four reads: their baseline is renewed
        # once a PSI_EVERY_S, so a stall's deltas cover `psi_window_s`
        psi, psi_t = _pressure_us(), prev.t
        self._last_tick = prev
        try:
            while not stop.wait(TICK_S):
                if time.monotonic() - prev.t - TICK_S >= STALL_S:
                    # FIRST the frames: this thread took the lock as its
                    # holder dropped it, and the holder has not moved yet
                    stacks = thread_stacks(ident)
                    now, psi_now = HostSample.take(), _pressure_us()
                    self._record(prev, now, psi, psi_now, stacks, now.t - psi_t)
                    psi, psi_t = psi_now, now.t
                prev = HostSample.take()
                if prev.t - psi_t >= PSI_EVERY_S:
                    psi, psi_t = _pressure_us(), prev.t
                self._last_tick = prev
        finally:
            self._last_tick = None

    def _record(self, prev, now, psi, psi_now, stacks: List[str], psi_window_s: float) -> None:
        stall_s = now.t - prev.t - TICK_S
        d = now.since(prev)
        gc_s = d["gc_s"] or 0.0
        # every sleep of this thread is a voluntary switch: a counter that
        # has not moved since the heartbeat started is dead (a sandboxed
        # kernel that fills no `ru_nvcsw`), and CPU time stands in for it
        lock_waits = (
            _less(now.nvcsw, prev.nvcsw)
            if (_less(now.nvcsw, self._first.nvcsw) or 0) > 0
            else None
        )
        if lock_waits is None:
            held = d["cpu_s"] >= 0.5 * stall_s  # somebody ran all through
        else:
            held = lock_waits >= (
                LOCK_WAIT_SHARE * stall_s / sys.getswitchinterval()
            )
        where = "; ".join(stacks)
        if held:
            # a collection holds the lock as any C call does: name it
            # first, then where every thread stands
            held_by = ("gc; " if gc_s >= 0.5 * stall_s else "") + where
        elif d["cpu_s"] < max(0.02, 0.1 * stall_s):
            held_by = NOT_SCHEDULED  # nothing of ours ran, and nobody waited
        else:
            # runnable and not run (see run_delay_s), or a short hold
            held_by = f"{HEARTBEAT_LATE}; {where}"
        attrs = {
            "cpu_s": d["cpu_s"],
            "run_delay_s": d["run_delay_s"],
            "lock_waits": lock_waits,
            "nivcsw": d["nivcsw"],
            "majflt": d["majflt"],
            "gc_s": d["gc_s"],
            "throttled_s": d["throttled_s"],
            "held_by": held_by,
        }
        for key, value in psi_now.items():
            attrs[key] = _less(value, psi.get(key))
        attrs["psi_window_s"] = round(psi_window_s, 3)
        self._total_s += max(0.0, stall_s - gc_s)
        self.count += 1
        self.last = {"t": now.t, "stall_s": round(stall_s, 6), **attrs}
        TRACER.add_span("stall.process", prev.t + TICK_S, now.t, attrs)


HEARTBEAT = Heartbeat()


def start() -> bool:
    """A scheduler starts: the collector's callback (once a process) and
    the heartbeat, where telemetry is on. Returns whether :func:`stop`
    has anything to undo."""
    if not enabled():
        return False
    GC_WATCH.install()
    HEARTBEAT.acquire()
    return True


def stop() -> None:
    HEARTBEAT.release()
    GC_WATCH.flush()
