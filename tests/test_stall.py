"""Why a pass of the serving loop ran long (obs/stall.py, obs/detect.py):
the host sample and its degradation, the one rule that names a cause, the
three forced cases (a collection, a process that was not scheduled, a
thread that kept the interpreter lock), the deltas on every ``sched.iter``,
and the kill switch."""

import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu import obs
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
    FakeBackend,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import stall
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
    ANOMALY_C,
    STALL_SECONDS_C,
    classify,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.flight import (
    EV_ANOMALY,
    FLIGHT,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
    ContinuousScheduler,
)

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def obs_on():
    was = obs.enabled()
    obs.enable()
    yield
    (obs.enable if was else obs.disable)()


@pytest.fixture
def obs_off():
    was = obs.enabled()
    obs.disable()
    yield
    (obs.enable if was else obs.disable)()


@pytest.fixture
def gc_watch_restored():
    """The collector's callback is installed once a process: a test that
    asserts on ``gc.callbacks`` puts it back as it found it."""
    had = stall.GC_WATCH._on_gc in gc.callbacks
    yield
    (stall.GC_WATCH.install if had else stall.GC_WATCH.uninstall)()


def _serve(requests=4, tokens=400, tokens_per_s=800.0, during=None):
    """A fake-session run of the continuous scheduler: ``requests``
    callers at once, ``during`` called once the loop has passes behind
    it. Returns the scheduler's ``/debug/state`` taken before the stop."""
    sched = ContinuousScheduler(
        FakeBackend(tokens_per_s=tokens_per_s, simulate_delay=True),
        slice_steps=16,
    )
    sched.start()
    try:
        callers = [
            threading.Thread(
                target=sched.submit,
                args=(GenerationRequest("m", "hello world " * 4, tokens),),
            )
            for _ in range(requests)
        ]
        for c in callers:
            c.start()
        if during is not None:
            time.sleep(0.3)  # 16 steps at 800 tokens/s: 20 ms a pass
            during()
        for c in callers:
            c.join(timeout=60)
        assert not any(c.is_alive() for c in callers)
        return sched.debug_state()
    finally:
        sched.stop()


# -- the host sample ------------------------------------------------------------


def test_host_sample_reads_this_platform(obs_on):
    a = stall.HostSample.take()
    sum(i * i for i in range(200_000))
    b = stall.HostSample.take()
    d = b.since(a)
    assert set(stall.DELTA_NAMES) <= set(d)
    assert d["thread_cpu_s"] > 0 and d["cpu_s"] >= d["thread_cpu_s"] * 0.5
    assert b.t > a.t and d["gc_n"] >= 0
    if Path(stall.SCHEDSTAT_PATH).exists():
        assert d["run_delay_s"] is not None and d["run_delay_s"] >= 0


def test_host_sample_degrades_to_none_and_never_raises(monkeypatch):
    """A platform without ``/proc/thread-self/schedstat``, without a
    readable ``cpu.stat`` and without ``RUSAGE_THREAD`` gives ``None``
    fields, and the deltas over them are ``None`` too."""
    monkeypatch.setattr(stall, "SCHEDSTAT_PATH", "/nonexistent/schedstat")
    monkeypatch.setattr(stall, "_schedstat_there", True)
    monkeypatch.setattr(stall, "CPU_STAT_PATHS", ("/nonexistent/cpu.stat",))
    monkeypatch.setattr(stall, "_cpu_stat_path", "/nonexistent/cpu.stat")

    def no_rusage(_who):
        raise ValueError("invalid who parameter")

    monkeypatch.setattr(stall.resource, "getrusage", no_rusage)
    a = stall.HostSample.take()
    b = stall.HostSample.take()
    assert (a.run_delay_ns, a.on_cpu_ns, a.throttled_s) == (None, None, None)
    assert (a.nivcsw, a.nvcsw, a.majflt) == (None, None, None)
    d = b.since(a)
    assert d["run_delay_s"] is None and d["throttled_s"] is None
    assert d["nivcsw"] is None and d["majflt"] is None
    assert d["cpu_s"] >= 0 and d["gc_s"] >= 0  # the clocks are always there
    # a path that never answered is not asked again
    assert stall._cpu_stat_path is None and stall._schedstat_there is False
    # a file of another shape is no number either
    monkeypatch.setattr(stall, "_read_text", lambda path: "garbage")
    monkeypatch.setattr(stall, "_schedstat_there", True)
    assert stall._schedstat() == (None, None)
    assert set(stall._pressure_us().values()) == {None}


def test_throttled_seconds_from_either_cgroup_version(monkeypatch):
    texts = {
        "/v2": "usage_usec 5\nnr_throttled 3\nthrottled_usec 2500000\n",
        "/v1": "nr_periods 1\nnr_throttled 1\nthrottled_time 1500000000\n",
    }
    monkeypatch.setattr(stall, "_read_text", texts.get)
    for path, want in (("/v2", 2.5), ("/v1", 1.5)):
        monkeypatch.setattr(stall, "CPU_STAT_PATHS", (path,))
        monkeypatch.setattr(stall, "_cpu_stat_path", path)
        assert stall._throttled_s() == pytest.approx(want)


# -- the rule ---------------------------------------------------------------------

QUIET = {"cpu_s": 0.01, "thread_cpu_s": 0.004, "run_delay_s": 0.0, "throttled_s": 0.0,
         "gc_s": 0.0, "gc_n": 0, "nivcsw": 0, "majflt": 0, "process_stall_s": 0.0,
         "compiles": 0}


@pytest.mark.parametrize(
    "deltas, phase_excess, cause",
    [
        ({"process_stall_s": 0.9}, {"wait": 0.95}, "process"),
        ({"compiles": 1, "gc_s": 0.9}, {"join": 0.9, "cpu": 0.9}, "compile"),
        ({"gc_s": 0.6, "gc_n": 1}, {"wait": 0.9}, "gc"),
        ({"throttled_s": 0.7}, {}, "throttled"),
        ({"run_delay_s": 0.5, "nivcsw": 40}, {"egress": 0.9}, "run_queue"),
        ({"majflt": 3}, {"egress": 0.9}, "page_fault"),
        ({}, {"wait": 0.8, "egress": 0.1}, "device_wait"),
        ({}, {"cpu": 0.7, "egress": 0.6, "join": 0.2}, "host:egress"),
        ({}, {"cpu": 0.9, "slice": 0.8}, "host:slice"),
        ({}, {"join": 0.9}, "unknown"),  # a phase grew, the thread's CPU did not: no counter says why
        ({}, {"join": 0.3, "egress": 0.1}, "unknown"),  # no phase holds half of it
        ({"gc_s": 0.2, "run_delay_s": 0.3}, {"wait": 0.4}, "unknown"),  # none explains half
        # two compete: a collection of 0.6 s inside a process stall of 0.9 s
        # of which the heartbeat took the collection's seconds off
        ({"process_stall_s": 0.3, "gc_s": 0.6}, {"wait": 1.0}, "gc"),
        # a fault while the thread's own CPU rose is the phase's, not the fault's
        ({"majflt": 1}, {"cpu": 0.8, "admit": 0.8}, "host:admit"),
        # a platform without the counters: None reads as nothing
        ({"run_delay_s": None, "throttled_s": None, "majflt": None}, {"wait": 0.6}, "device_wait"),
    ],
    ids=["process", "compile", "gc", "throttled", "run_queue", "page_fault", "device_wait",
         "host-egress", "host-slice", "unknown-join", "unknown-phase", "unknown-split", "gc-in-process-stall",
         "fault-on-cpu", "none-fields"],
)
def test_classify_names_the_first_cause_that_explains_half(deltas, phase_excess, cause):
    assert classify(1.0, {**QUIET, **deltas}, phase_excess) == cause


# -- the forced cases ---------------------------------------------------------------


def test_a_collection_inside_a_pass_is_a_gc_span_and_a_pass_stall_of_cause_gc(obs_on):
    junk = [[] for _ in range(1_500_000)]
    for cell in junk:
        cell.append(cell)  # cycles: the collector has to walk every one
    FLIGHT.clear()
    seq0 = TRACER.seq()
    fired0 = ANOMALY_C.labels(kind="pass_stall").value
    seconds0 = STALL_SECONDS_C.labels(cause="gc").value
    state = _serve(tokens=800, during=gc.collect)
    del junk
    spans = TRACER.spans(since=seq0)
    collections = [s for s in spans if s.name == "gc" and s.attrs.get("generation") == 2]
    assert collections and max(s.dur_s for s in collections) > 0.05
    stalls = [s for s in spans if s.name == "stall"]
    assert [s.attrs["cause"] for s in stalls] == ["gc"], [s.attrs for s in stalls]
    stalled = stalls[0]
    assert stalled.attrs["gc_s"] >= 0.5 * stalled.attrs["excess_s"] > 0.025
    # the pass that held it says so on its own span, and the stall hangs under it
    iters = {s.span_id: s for s in spans if s.name == "sched.iter"}
    assert iters[stalled.parent_id].attrs["gc_s"] == stalled.attrs["gc_s"]
    anomalies = [e for e in FLIGHT.events(type_=EV_ANOMALY) if e["kind"] == "pass_stall"]
    assert len(anomalies) == 1 and anomalies[0]["cause"] == "gc"
    assert anomalies[0]["stream"] == "sched_pass" and "exemplar" in anomalies[0]
    assert ANOMALY_C.labels(kind="pass_stall").value == fired0 + 1
    assert STALL_SECONDS_C.labels(cause="gc").value - seconds0 == pytest.approx(
        stalled.attrs["excess_s"], abs=1e-5)
    # /debug/state: the same counters
    assert state["stalls"]["count"] == 1 and set(state["stalls"]["by_cause"]) == {"gc"}
    assert state["stalls"]["seconds"] == pytest.approx(stalled.attrs["excess_s"], abs=1e-5)
    assert state["stalls"]["last"]["cause"] == "gc" and state["stalls"]["passes"] > 8
    assert set(state["stalls"]["process"]) == {"count", "last"}


def test_a_collection_under_the_rings_lock_does_not_wait_for_it(obs_on, gc_watch_restored):
    """The callback runs on whichever thread allocated, also one that is
    inside ``TRACER.spans()`` or ``add_span`` with the ring's lock, which
    is not reentrant: it takes no lock, and the ``gc`` span reaches the
    ring at the next host sample."""
    stall.GC_WATCH.install()
    stall.GC_WATCH.flush()
    seq0 = TRACER.seq()
    done = threading.Event()

    def collect_locked():
        with TRACER._lock:
            gc.collect()  # generation 2: start and stop under the lock
        done.set()

    threading.Thread(target=collect_locked, daemon=True).start()
    assert done.wait(20.0), "the collector's callback waits for a lock its own thread holds"
    assert not [s for s in TRACER.spans(since=seq0) if s.name == "gc"]
    sample = stall.HostSample.take()
    collections = [s for s in TRACER.spans(since=seq0) if s.name == "gc"]
    assert collections and collections[-1].attrs["generation"] == 2
    assert collections[-1].attrs["collected"] >= 0 and sample.gc_n >= 1
    assert sample.gc_s >= collections[-1].dur_s > 0


HEARTBEAT_CHILD = """
import json, sys, time
sys.path.insert(0, {root!r})
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import stall
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
assert stall.start()
print("ready", flush=True)
sys.stdin.readline()  # the test has stopped and continued us by now
time.sleep(0.2)
stall.stop()
print(json.dumps([dict(s.attrs, span_s=s.dur_s) for s in TRACER.spans() if s.name == "stall.process"]))
"""


def test_a_stopped_process_reports_process_not_scheduled():
    env = {**os.environ, "TPU_LLM_OBS": "1"}
    child = subprocess.Popen(
        [sys.executable, "-c", HEARTBEAT_CHILD.format(root=str(ROOT))],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.2)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.5)
        child.send_signal(signal.SIGCONT)
        out, _ = child.communicate("go\n", timeout=30)
    finally:
        child.kill()
    assert child.returncode == 0
    stalls = json.loads(out.strip().splitlines()[-1])
    assert len(stalls) == 1, stalls
    assert stalls[0]["held_by"] == stall.NOT_SCHEDULED
    assert 0.3 < stalls[0]["span_s"] < 1.5 and stalls[0]["cpu_s"] < 0.05
    assert stalls[0]["lock_waits"] <= 5


def hold_the_lock(data):
    """One C call that keeps the interpreter lock all through."""
    return sorted(data)


@pytest.mark.parametrize("switches_counted", [True, False], ids=["kernel-counts", "sandbox-zeros"])
def test_a_thread_that_kept_the_lock_is_named_by_its_frame(obs_on, monkeypatch, switches_counted):
    """The witness is the heartbeat's own voluntary switches (a thread that
    waits for the lock asks again every 5 ms); under a kernel that fills
    none (the chip's sandbox: every ``ru_nvcsw`` reads 0) the process's
    CPU time stands in."""
    import random
    import types

    if not switches_counted:
        monkeypatch.setattr(
            stall.resource, "getrusage",
            lambda who: types.SimpleNamespace(ru_nivcsw=0, ru_nvcsw=0, ru_majflt=0),
        )
    rng = random.Random(7)
    data = [rng.random() for _ in range(1_500_000)]
    seq0 = TRACER.seq()
    assert stall.start()
    try:
        time.sleep(0.15)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.4:
            hold_the_lock(data)
        time.sleep(0.15)
    finally:
        stall.stop()
    stalls = [s for s in TRACER.spans(since=seq0) if s.name == "stall.process"]
    assert stalls, "no overshoot of the heartbeat's sleep was recorded"
    held = max(stalls, key=lambda s: s.dur_s)
    assert "hold_the_lock" in held.attrs["held_by"], held.attrs
    assert "test_stall.py" in held.attrs["held_by"]
    assert held.attrs["cpu_s"] > 0.05
    if switches_counted:
        assert held.attrs["lock_waits"] >= 5
    else:
        assert held.attrs["lock_waits"] is None
    assert not [t for t in threading.enumerate() if t.name == "stall-heartbeat"]


# -- the loop -------------------------------------------------------------------------


def test_every_pass_carries_the_eight_deltas_and_the_heartbeat_runs(obs_on):
    seq0 = TRACER.seq()
    seen = []
    state = _serve(tokens=200, during=lambda: seen.extend(t.name for t in threading.enumerate()))
    assert "stall-heartbeat" in seen
    assert stall.GC_WATCH._on_gc in gc.callbacks
    iters = [s for s in TRACER.spans(since=seq0) if s.name == "sched.iter"]
    assert len(iters) > 8
    for s in iters:
        assert set(stall.DELTA_NAMES) <= set(s.attrs), s.attrs
        assert s.attrs["cpu_s"] >= 0 and s.attrs["gc_n"] >= 0
    # the detector is fed the passes that ran a slice (the fake twin
    # reports the seconds its slice waited, as the real session does)
    slices = [s for s in TRACER.spans(since=seq0) if s.name == "sched.slice"]
    # (the callers return inside the last pass, before it closes)
    assert len(slices) - 1 <= state["stalls"]["passes"] <= len(slices)
    assert len(slices) >= len(iters) - 2
    assert not [t for t in threading.enumerate() if t.name == "stall-heartbeat"]


def test_with_telemetry_off_nothing_of_it_runs(obs_off, gc_watch_restored, monkeypatch):
    stall.GC_WATCH.uninstall()
    callbacks = list(gc.callbacks)
    opened = []
    monkeypatch.setattr(stall, "_read_text", lambda path: opened.append(path))
    seq0 = TRACER.seq()
    seen = []
    state = _serve(tokens=200, during=lambda: seen.extend(t.name for t in threading.enumerate()))
    assert "batch-scheduler" in seen and "stall-heartbeat" not in seen
    assert gc.callbacks == callbacks
    assert opened == []
    assert TRACER.spans(since=seq0) == []
    assert state["stalls"]["passes"] == 0 and state["stalls"]["count"] == 0
    assert stall.start() is False
