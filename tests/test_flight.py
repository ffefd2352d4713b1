"""Flight recorder, debug introspection endpoints, and streaming
anomaly detection (ISSUE 5).

Acceptance surface: the ring buffer survives concurrent writers with
drop-oldest accounting and no event tearing; a poisoned ticket through
the window scheduler's bisection fallback leaves a crash dump (last
events + live scheduler state); ``/debug/state`` and ``/debug/flight``
return live session state and seq-ordered events with trace ids linking
back to spans (and 404 under the kill switch); the Welford cell-CV
tracker and the rolling-median spike detector fire anomaly events; the
stepped decode path exports goodput counters.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu import obs
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
    FakeBackend,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
    CellCvTracker,
    SpikeDetector,
    Welford,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.flight import (
    EV_ANOMALY,
    EV_REQUEST_ADMITTED,
    EV_ROW_RETIRED,
    EV_SLICE,
    FLIGHT,
    FlightRecorder,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
    REGISTRY,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.server import (
    GenerationServer,
)


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


def _get_json(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


def _post_generate(base: str, prompt: str, num_predict: int):
    req = urllib.request.Request(
        f"{base}/api/generate",
        data=json.dumps(
            {
                "model": "m",
                "prompt": prompt,
                "options": {"num_predict": num_predict},
            }
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


# -- ring buffer ---------------------------------------------------------------


def test_ring_records_schema_and_order(obs_on):
    rec = FlightRecorder(capacity=16)
    rec.emit("a", trace=7, x=1)
    rec.emit("b")
    events = rec.events()
    assert [e["type"] for e in events] == ["a", "b"]
    assert events[0]["seq"] < events[1]["seq"]
    assert events[0]["trace"] == 7 and events[0]["x"] == 1
    assert "trace" not in events[1]  # no request context, no key
    assert rec.summary()["by_type"] == {"a": 1, "b": 1}
    assert rec.summary()["dropped"] == 0


def test_ring_drop_oldest_counts_dropped(obs_on):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.emit("e", i=i)
    events = rec.events()
    assert len(events) == 4
    # oldest aged out: the ring holds the LAST four, in order
    assert [e["i"] for e in events] == [6, 7, 8, 9]
    s = rec.summary()
    assert s["dropped"] == 6 and s["events_total"] == 10


def test_ring_filters_and_limits(obs_on):
    rec = FlightRecorder(capacity=64)
    for i in range(6):
        rec.emit("a" if i % 2 else "b", i=i)
    assert [e["i"] for e in rec.events(n=2)] == [4, 5]
    assert [e["i"] for e in rec.events(type_="a")] == [1, 3, 5]


def test_ring_concurrent_writers_no_tearing(obs_on):
    """8 writers × 200 events through a 256-slot ring: every surviving
    event is whole (all schema fields, writer-local order preserved),
    accounting is exact (total == seq high-water == kept + dropped)."""
    rec = FlightRecorder(capacity=256)
    n_threads, per_thread = 8, 200

    def writer(tid):
        for i in range(per_thread):
            rec.emit("w", tid=tid, i=i)

    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = rec.events()
    s = rec.summary()
    assert s["events_total"] == n_threads * per_thread
    assert len(events) == 256
    assert s["dropped"] == n_threads * per_thread - 256
    # no tearing: every event carries its full schema and the ring is
    # strictly seq-ordered
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    per_writer = {}
    for e in events:
        assert {"seq", "t_s", "type", "tid", "i"} <= set(e)
        per_writer.setdefault(e["tid"], []).append(e["i"])
    # writer-local order survives interleaving
    for order in per_writer.values():
        assert order == sorted(order)


def test_ring_export_jsonl(obs_on, tmp_path):
    rec = FlightRecorder(capacity=8)
    rec.emit("x", k="v")
    out = tmp_path / "flight.jsonl"
    assert rec.export_jsonl(out) == 1
    line = json.loads(out.read_text().splitlines()[0])
    assert line["type"] == "x" and line["k"] == "v"


def test_ring_emit_noop_when_disabled(obs_off):
    rec = FlightRecorder(capacity=8)
    assert rec.emit("dead") is None
    assert rec.events() == []
    assert rec.summary()["events_total"] == 0
    assert rec.crash_dump("dead") is None


# -- crash dump ----------------------------------------------------------------


def test_crash_dump_writes_events_and_state(obs_on, tmp_path):
    rec = FlightRecorder(capacity=8)
    rec.emit("before_crash", step=1)
    path = rec.crash_dump(
        "test failure", state={"queue_depth": 3}, path=tmp_path / "dump.json"
    )
    payload = json.loads((tmp_path / "dump.json").read_text())
    assert path == str(tmp_path / "dump.json")
    assert payload["reason"] == "test failure"
    assert payload["state"] == {"queue_depth": 3}
    assert any(e["type"] == "before_crash" for e in payload["events"])
    # the dump itself is on the record
    assert rec.events(type_="crash_dump")


def test_crash_dump_never_raises(obs_on, tmp_path):
    rec = FlightRecorder(capacity=8)
    # unwritable destination: returns None instead of raising
    assert (
        rec.crash_dump("x", path=tmp_path / "no" / "such" / "dir" / "f.json")
        is None
    )


def test_poisoned_window_batch_leaves_crash_dump(obs_on, tmp_path, monkeypatch):
    """A poisoned ticket that kills the window batch dispatch triggers
    the bisection fallback AND writes a crash dump (last events + live
    scheduler state) into TPU_LLM_CRASH_DIR."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        BatchScheduler,
    )

    monkeypatch.setenv("TPU_LLM_CRASH_DIR", str(tmp_path))

    class OnePoisonBackend(FakeBackend):
        def generate(self, request):
            if request.prompt == "poison":
                raise RuntimeError("bad request")
            return super().generate(request)

        def generate_batch(self, requests):
            if any(r.prompt == "poison" for r in requests):
                raise RuntimeError("batch poisoned")
            return [self.generate(r) for r in requests]

    sched = BatchScheduler(OnePoisonBackend(), window_s=0.05, max_batch=8)
    sched.start()
    results, errors = {}, {}

    def call(prompt):
        try:
            results[prompt] = sched.submit(
                GenerationRequest("m", prompt, max_new_tokens=4)
            )
        except Exception as exc:  # noqa: BLE001
            errors[prompt] = exc

    try:
        threads = [
            threading.Thread(target=call, args=(p,))
            for p in ("a", "b", "poison", "c")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sched.stop()
    assert set(results) == {"a", "b", "c"} and set(errors) == {"poison"}
    dumps = list(tmp_path.glob("flight_crash_*.json"))
    assert dumps, "no crash dump written"
    payload = json.loads(dumps[0].read_text())
    assert "window batch dispatch failed" in payload["reason"]
    assert payload["state"]["mode"] == "window"
    # the dump's event tail contains the batch's admissions and the
    # fallback that killed it
    types = {e["type"] for e in payload["events"]}
    assert EV_REQUEST_ADMITTED in types
    assert "batch_fallback" in types


# -- debug endpoints -----------------------------------------------------------


def test_debug_endpoints_serve_live_state_and_events(obs_on):
    FLIGHT.clear()
    srv = GenerationServer(
        FakeBackend(),
        host="127.0.0.1",
        port=0,
        quiet=True,
        scheduler="continuous",
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = _post_generate(base, "hello", 8)
        assert body.get("done"), body

        state = _get_json(f"{base}/debug/state")
        assert state["scheduler_mode"] == "continuous"
        assert state["backend"] == "FakeBackend"
        assert state["scheduler"]["mode"] == "continuous"
        assert state["scheduler"]["queue_depth"] == 0
        assert state["flight"]["events_total"] > 0

        flight = _get_json(f"{base}/debug/flight?n=100")
        events = flight["events"]
        types = [e["type"] for e in events]
        assert EV_REQUEST_ADMITTED in types
        assert EV_SLICE in types
        assert EV_ROW_RETIRED in types
        # seq-ordered, and the request's admitted precedes its retired
        # with ONE trace id linking them (and the span tree)
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs)
        admitted = next(e for e in events if e["type"] == EV_REQUEST_ADMITTED)
        retired = next(
            e
            for e in events
            if e["type"] == EV_ROW_RETIRED
            and e.get("trace") == admitted.get("trace")
        )
        assert admitted.get("trace") is not None
        assert admitted["seq"] < retired["seq"]

        # ?type= filter and ?n= bound
        only = _get_json(f"{base}/debug/flight?n=2&type={EV_SLICE}")
        assert all(e["type"] == EV_SLICE for e in only["events"])
        assert len(only["events"]) <= 2
    finally:
        srv.stop()


def test_debug_flight_rejects_bad_n(obs_on):
    srv = GenerationServer(FakeBackend(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/flight?n=bogus", timeout=10
            )
        assert exc_info.value.code == 400
    finally:
        srv.stop()


def test_debug_endpoints_404_when_disabled(obs_off):
    """Kill-switch completeness: the debug surface is OFF with telemetry
    off — same contract as /metrics."""
    srv = GenerationServer(FakeBackend(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        for path in ("/debug/state", "/debug/flight", "/debug/flight?n=5"):
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=10
                )
            assert exc_info.value.code == 404, path
    finally:
        srv.stop()


def test_kill_switch_served_request_emits_no_events(obs_off):
    """With telemetry off a served request leaves ZERO flight events —
    the scheduler/engine emit calls are no-ops."""
    before = FLIGHT.summary()["events_total"]
    srv = GenerationServer(
        FakeBackend(),
        host="127.0.0.1",
        port=0,
        quiet=True,
        scheduler="continuous",
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = _post_generate(base, "quiet", 4)
        assert body.get("done"), body
    finally:
        srv.stop()
    assert FLIGHT.summary()["events_total"] == before


# -- goodput accounting --------------------------------------------------------


def test_goodput_counters_from_stepped_session(obs_on):
    """The stepped decode path exports llm_engine_goodput_tokens_total
    (tokens on completed rows) vs llm_engine_stepped_tokens_total (every
    row x step the bucket executed): goodput <= stepped, both move."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
        GOODPUT_C,
        STEPPED_C,
    )

    good0 = GOODPUT_C.labels().value
    step0 = STEPPED_C.labels().value
    backend = FakeBackend()
    session = backend.decode_open(
        [
            GenerationRequest("m", "one", max_new_tokens=6),
            GenerationRequest("m", "two", max_new_tokens=20),
        ]
    )
    while session.active:
        session.step(8)
    session.close()
    good = GOODPUT_C.labels().value - good0
    stepped = STEPPED_C.labels().value - step0
    assert good == 6 + 20
    # rows step whole slices: the 6-token row rode 8 steps, the 20-token
    # row 24 — the overshoot is exactly the wasted-step fraction
    assert stepped > good
    text = REGISTRY.exposition()
    assert "llm_engine_goodput_tokens_total" in text
    assert "llm_engine_stepped_tokens_total" in text


def test_goodput_counters_real_engine_stepped(obs_on):
    """Same invariant on the REAL stepped engine (tiny CPU config)."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
        GOODPUT_C,
        STEPPED_C,
    )

    good0 = GOODPUT_C.labels().value
    step0 = STEPPED_C.labels().value
    engine = JaxEngine(
        registry={"tiny": get_model_config("qwen2:1.5b").tiny()},
        dtype=jnp.float32,
    )
    session = engine.decode_open(
        [
            GenerationRequest(
                "tiny", "a", max_new_tokens=4, stop_at_eos=False
            ),
            GenerationRequest(
                "tiny", "bb", max_new_tokens=10, stop_at_eos=False
            ),
        ]
    )
    while session.active:
        session.step()
    session.close()
    good = GOODPUT_C.labels().value - good0
    stepped = STEPPED_C.labels().value - step0
    # both rows completed: first tokens came from prefill, the decode
    # loop sampled the rest (max_new_tokens - 1 each at minimum)
    assert good >= (4 - 1) + (10 - 1)
    assert stepped > good  # padding slots + the short row's done steps


# -- Welford / cell CV ---------------------------------------------------------


def test_welford_matches_statistics():
    import statistics

    xs = [3.1, 2.9, 3.0, 3.3, 2.8, 3.05]
    w = Welford()
    for x in xs:
        w.update(x)
    assert w.count == len(xs)
    assert w.mean == pytest.approx(statistics.fmean(xs))
    assert w.std == pytest.approx(statistics.stdev(xs))
    assert w.cv == pytest.approx(statistics.stdev(xs) / statistics.fmean(xs))


def test_welford_cv_none_until_two_runs():
    w = Welford()
    assert w.cv is None
    w.update(5.0)
    assert w.cv is None
    w.update(5.0)
    assert w.cv == 0.0


def test_cell_cv_gauge_and_anomaly_once_per_breach(obs_on):
    FLIGHT.clear()
    tracker = CellCvTracker(threshold=0.05, min_runs=3)
    # a stable cell: CV well under the threshold, no anomaly
    for x in (100.0, 101.0, 99.5, 100.4):
        tracker.observe_run("qwen2:1.5b", 100, "on_device", energy_J=x)
    assert not FLIGHT.events(type_=EV_ANOMALY)
    # a noisy cell breaches after min_runs... once, not per run
    for x in (100.0, 160.0, 60.0, 150.0):
        tracker.observe_run("qwen2:1.5b", 500, "remote", energy_J=x)
    anomalies = FLIGHT.events(type_=EV_ANOMALY)
    assert len(anomalies) == 1
    a = anomalies[0]
    assert a["kind"] == "cell_cv" and a["model"] == "qwen2:1.5b"
    assert a["location"] == "remote" and a["cv"] > 0.05
    # the gauge family is exported with the cell's labels
    text = REGISTRY.exposition()
    assert "llm_run_cell_cv" in text
    assert (
        'llm_run_cell_cv{metric="energy_J",model="qwen2:1.5b",'
        'length="500",location="remote"}' in text
    )
    snap = tracker.snapshot()
    assert snap["energy_J|qwen2:1.5b|500|remote"]["breached"] is True
    assert snap["energy_J|qwen2:1.5b|100|on_device"]["breached"] is False


def test_cell_cv_rearm_after_recovery(obs_on):
    FLIGHT.clear()
    tracker = CellCvTracker(threshold=0.05, min_runs=2)
    tracker.observe_run("m", 1, "l", wall_s=1.0)
    tracker.observe_run("m", 1, "l", wall_s=2.0)  # breach #1
    assert len(FLIGHT.events(type_=EV_ANOMALY)) == 1
    # many identical runs drag the CV back under the threshold → re-arm
    for _ in range(200):
        tracker.observe_run("m", 1, "l", wall_s=1.5)
    key = ("wall_s", "m", "1", "l")
    assert key not in tracker._breached
    tracker.observe_run("m", 1, "l", wall_s=30.0)  # breach #2 fires again
    assert len(FLIGHT.events(type_=EV_ANOMALY)) == 2


def test_cell_cv_noop_when_disabled(obs_off):
    tracker = CellCvTracker()
    out = tracker.observe_run("m", 1, "l", energy_J=5.0, wall_s=1.0)
    assert out == {} and tracker.snapshot() == {}


def test_cell_cv_wired_through_study_run_data(obs_on, tmp_path):
    """The runner path: populate_run_data folds the run's modelled J and
    wall into the cell tracker (llm_run_cell_cv visible mid-study)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationResult,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        LlmEnergyConfig,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import CELL_CV
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.context import (
        RunContext,
    )

    CELL_CV.reset()
    config = LlmEnergyConfig(
        models=["qwen2:1.5b"], locations=["on_device"], lengths=[100],
        repetitions=2, backends={"on_device": FakeBackend()},
    )
    for i in range(2):
        run_dir = tmp_path / f"run_{i}"
        run_dir.mkdir()
        ctx = RunContext(
            run_id=f"run_{i}",
            run_nr=i + 1,
            total_runs=2,
            variation={
                "model": "qwen2:1.5b", "location": "on_device", "length": 100,
            },
            run_dir=run_dir,
            experiment_dir=tmp_path,
        )
        request = GenerationRequest("qwen2:1.5b", "t", max_new_tokens=8)
        result = GenerationResult(
            request=request, tokens=[1] * 8, text="x", prompt_tokens=2,
            generated_tokens=8, prefill_s=0.01, decode_s=0.4 + 0.01 * i,
            total_s=0.41 + 0.01 * i,
        )
        ctx.scratch["result"] = result
        ctx.scratch["topic"] = "t"
        ctx.scratch["generation_stats"] = {
            "flops": 1e9, "bytes": 1e8, "vpu_ops": 0.0,
            "duration_s": result.decode_s,
            "generated_tokens": 8,
        }
        row = config.populate_run_data(ctx)
        assert row is not None
    snap = CELL_CV.snapshot()
    key = "energy_J|qwen2:1.5b|100|on_device"
    assert snap[key]["runs"] == 2
    assert snap[key]["cv"] is not None
    assert "wall_s|qwen2:1.5b|100|on_device" in snap


# -- spike detection -----------------------------------------------------------


def test_spike_detector_fires_with_exemplar(obs_on):
    FLIGHT.clear()
    FLIGHT.emit("slice", i=1)
    FLIGHT.emit("slice", i=2)
    det = SpikeDetector("test_stream", min_samples=8)
    for _ in range(10):
        assert det.observe(0.010) is False
    assert det.observe(0.100, trace=42) is True
    anomalies = FLIGHT.events(type_=EV_ANOMALY)
    assert len(anomalies) == 1
    a = anomalies[0]
    assert a["kind"] == "pass_stall" and a["stream"] == "test_stream"
    assert a["trace"] == 42
    assert a["dur_s"] == pytest.approx(0.1)
    assert a["median_s"] == pytest.approx(0.01)
    assert a["excess_s"] == pytest.approx(0.09)
    assert a["cause"] == "unknown"  # no host account was given
    # the exemplar carries the recorder's recent context
    assert [e["type"] for e in a["exemplar"]][:2] == ["slice", "slice"]
    assert det.snapshot()["by_cause"] == {"unknown": pytest.approx(0.09)}


@pytest.mark.parametrize(
    "prior, min_samples, observed, expected",
    [
        # over the median by more than 50 ms: long
        ([0.010] * 10, 8, [0.100], [True]),
        # 4x the median and more, but under the 50-ms floor: not long
        # (the rule the 4x multiple was, read the other way round)
        ([0.010] * 10, 8, [0.055], [False]),
        # a 185-ms period that ran 120 ms long is 1.65x: the old rule's
        # blind spot, and the case the records describe
        ([0.185] * 10, 8, [0.305], [True]),
        # a long period's share: 15% of 1 s is 150 ms
        ([1.0] * 10, 8, [1.10, 1.20], [False, True]),
        # a long pass must not drag the median up and mask its
        # successors: an identical second one still fires
        ([0.010] * 8, 4, [1.0, 1.0], [True, True]),
        # quiet until the window is armed
        ([0.01] * 7, 8, [5.0], [False]),
    ],
    ids=["over-floor", "under-floor", "period-1.65x", "share", "excluded",
         "unarmed"],
)
def test_pass_stall_excess_rule(obs_on, prior, min_samples, observed, expected):
    det = SpikeDetector("s", min_samples=min_samples)
    for dur in prior:
        assert det.observe(dur) is False
    assert [det.observe(dur) for dur in observed] == expected


def test_compiling_slice_is_its_own_anomaly_and_still_observed(
    obs_on, monkeypatch
):
    """A slice whose session reports a compile is flagged on its event,
    fires a compile_in_slice anomaly, and its pass STILL enters the
    detector of long passes: slow for a known reason is not the same as
    not slow."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import fake

    monkeypatch.setattr(
        fake._FakeStepSession, "last_slice_compiled", True, raising=False
    )
    FLIGHT.clear()
    srv = GenerationServer(
        FakeBackend(), host="127.0.0.1", port=0, quiet=True,
        scheduler="continuous",
    )
    srv.start()
    try:
        body = _post_generate(f"http://127.0.0.1:{srv.port}", "hello", 8)
        assert body.get("done"), body
    finally:
        srv.stop()
    slices = FLIGHT.events(type_=EV_SLICE)
    assert slices and all(e.get("compiled") is True for e in slices)
    anomalies = FLIGHT.events(type_=EV_ANOMALY)
    assert [a["kind"] for a in anomalies] == ["compile_in_slice"] * len(slices)
    assert srv._scheduler.debug_state()["stalls"]["passes"] == len(slices)


def test_spike_detector_noop_when_disabled(obs_off):
    det = SpikeDetector("s", min_samples=1)
    for _ in range(10):
        assert det.observe(0.01) is False
    assert det.observe(100.0) is False
