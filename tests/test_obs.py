"""Observability subsystem: metrics registry, span tracer, energy bridge,
and the instrumented serving path.

ISSUE 2 acceptance surface: ``/metrics`` exposes scheduler/engine/KV/
energy families after a served request; a request through
``BatchScheduler`` yields a queue→prefill→decode span tree under the
HTTP request's root with a finite J/token estimate; the kill switch
yields zero spans and a 404 ``/metrics``.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu import obs
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
    MetricsRegistry,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import (
    TRACER,
    SpanTracer,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import FakeBackend
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.server import (
    GenerationServer,
)


@pytest.fixture
def obs_on():
    """Guarantee telemetry is on for the test and restored after."""
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


def _tiny_registry():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    return {"tiny": get_model_config("qwen2:1.5b").tiny()}


# -- metrics registry ---------------------------------------------------------


def test_counter_gauge_and_exposition(obs_on):
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "help text", labels=("path",))
    c.labels(path="/x").inc()
    c.labels(path="/x").inc(2)
    g = reg.gauge("t_gauge", "g")
    g.set(3.5)
    text = reg.exposition()
    assert "# TYPE t_requests_total counter" in text
    assert 't_requests_total{path="/x"} 3.0' in text
    assert "# HELP t_requests_total help text" in text
    assert "t_gauge 3.5" in text


def test_histogram_buckets_are_cumulative(obs_on):
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_seconds", "h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = reg.exposition()
    assert 't_lat_seconds_bucket{le="0.1"} 1' in text
    assert 't_lat_seconds_bucket{le="1.0"} 3' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 4' in text
    assert "t_lat_seconds_count 4" in text
    assert "t_lat_seconds_sum 6.05" in text


def test_registry_families_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("t_same", "x")
    assert reg.counter("t_same", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("t_same")


def test_snapshot_shape(obs_on):
    reg = MetricsRegistry()
    reg.counter("t_c").inc(2)
    reg.histogram("t_h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["t_c"]["_"] == 2
    assert snap["t_h"]["_"]["count"] == 1
    assert snap["t_h"]["_"]["sum"] == 0.5


def test_exposition_golden_output(obs_on):
    """Pin the FULL text exposition against the v0.0.4 format spec:
    family sort, stable (sorted) child label order independent of
    first-touch order, label-value escaping (backslash, quote, newline),
    HELP escaping, cumulative buckets ending in +Inf == _count, and
    _count/_sum consistency. Any formatting drift breaks this test."""
    reg = MetricsRegistry()
    c = reg.counter("g_req_total", "requests", labels=("path", "code"))
    # touch children OUT of sorted order: exposition must sort them
    c.labels(path="/z", code="500").inc(2)
    c.labels(path="/a", code="200").inc(1)
    c.labels(path='/esc"\\x\n', code="200").inc(3)
    g = reg.gauge("g_rows", "live rows")
    g.set(4)
    h = reg.histogram("g_lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    golden = (
        "# HELP g_lat_seconds latency\n"
        "# TYPE g_lat_seconds histogram\n"
        'g_lat_seconds_bucket{le="0.1"} 1\n'
        'g_lat_seconds_bucket{le="1.0"} 2\n'
        'g_lat_seconds_bucket{le="+Inf"} 3\n'
        "g_lat_seconds_sum 2.55\n"
        "g_lat_seconds_count 3\n"
        "# HELP g_req_total requests\n"
        "# TYPE g_req_total counter\n"
        'g_req_total{path="/a",code="200"} 1.0\n'
        'g_req_total{path="/esc\\"\\\\x\\n",code="200"} 3.0\n'
        'g_req_total{path="/z",code="500"} 2.0\n'
        "# HELP g_rows live rows\n"
        "# TYPE g_rows gauge\n"
        "g_rows 4.0\n"
    )
    assert reg.exposition() == golden


def test_exposition_help_escaping(obs_on):
    reg = MetricsRegistry()
    reg.counter("g_c", "line one\nline two \\ slash").inc()
    text = reg.exposition()
    assert "# HELP g_c line one\\nline two \\\\ slash\n" in text


def test_kill_switch_silences_metrics_and_spans(obs_off):
    reg = MetricsRegistry()
    reg.counter("t_dead").inc(5)
    assert reg.exposition() == ""
    tracer = SpanTracer()
    with tracer.span("nothing"):
        tracer.add_span("inner", 0.0, 1.0)
    assert tracer.spans() == []


# -- tracer -------------------------------------------------------------------


def test_span_parent_links_and_chrome_export(obs_on, tmp_path):
    tracer = SpanTracer()
    with tracer.span("root", kind="test") as root:
        with tracer.span("child"):
            pass
        tracer.add_span("timed", 1.0, 2.0)
    spans = {s.name: s for s in tracer.spans()}
    assert spans["child"].parent_id == spans["root"].span_id
    assert spans["timed"].parent_id == spans["root"].span_id
    assert spans["root"].parent_id is None
    assert spans["timed"].dur_s == pytest.approx(1.0)
    out = tmp_path / "trace.json"
    tracer.export(out)
    events = json.loads(out.read_text())["traceEvents"]
    assert {e["name"] for e in events} == {"root", "child", "timed"}
    timed = next(e for e in events if e["name"] == "timed")
    assert timed["ph"] == "X" and timed["dur"] == pytest.approx(1e6)
    assert timed["args"]["parent_id"] == spans["root"].span_id


def test_attach_carries_parent_across_threads(obs_on):
    tracer = SpanTracer()
    with tracer.span("root") as root:
        def worker():
            with tracer.attach(root):
                tracer.add_span("hop", 0.0, 0.5)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {s.name: s for s in tracer.spans()}
    assert spans["hop"].parent_id == spans["root"].span_id


# -- energy bridge ------------------------------------------------------------


def test_energy_estimate_bounds_bracket_nominal(obs_on):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.energy import (
        estimate_from_stats,
    )

    est = estimate_from_stats(
        {
            "flops": 1e12,
            "bytes": 5e10,
            "vpu_ops": 1e9,
            "duration_s": 1.0,
            "generated_tokens": 100,
        }
    )
    assert est is not None
    assert est["J_low"] < est["J"] < est["J_high"]
    assert (
        est["J_per_token_low"]
        < est["J_per_token"]
        < est["J_per_token_high"]
    )
    assert est["J_per_token"] == pytest.approx(est["J"] / 100, rel=1e-3)


def test_energy_estimate_none_without_window(obs_on):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.energy import (
        estimate_from_stats,
    )

    assert estimate_from_stats({}) is None
    assert estimate_from_stats({"duration_s": 0.0}) is None


# -- served path (the acceptance criteria) ------------------------------------


def test_metrics_endpoint_after_served_request(obs_on):
    """/metrics exposition parses and contains the HTTP + scheduler
    families after one request through continuous batching."""
    srv = GenerationServer(
        FakeBackend(), host="127.0.0.1", port=0, quiet=True,
        batch_window_ms=20,
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/api/generate",
            data=json.dumps(
                {"model": "m", "prompt": "p", "options": {"num_predict": 4}}
            ).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read())["done"]
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
    finally:
        srv.stop()
    for family in (
        "llm_http_requests_total",
        "llm_http_request_seconds",
        "llm_sched_queue_wait_seconds",
        "llm_sched_window_collect_seconds",
        "llm_sched_admission_cap_rows",
        "llm_sched_batch_rows",
    ):
        assert family in text, family
    # the exposition parses: every sample line is "name{...} value"
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)
        assert name_part.startswith("llm_")


def test_metrics_endpoint_404_when_disabled(obs_off):
    srv = GenerationServer(FakeBackend(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10
            )
        assert exc_info.value.code == 404
    finally:
        srv.stop()


def test_kill_switch_covers_flight_and_debug_surface(obs_off):
    """Kill-switch completeness (ISSUE 5): with telemetry off the NEW
    surface is off too — flight emits are no-ops, the detectors stay
    silent, and both debug endpoints 404 (deep coverage incl. the
    concurrency/ordering cases lives in tests/test_flight.py)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
        SpikeDetector,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.flight import (
        FlightRecorder,
    )

    rec = FlightRecorder(capacity=4)
    assert rec.emit("dead") is None and rec.events() == []
    det = SpikeDetector("s", min_samples=1)
    det.observe(0.001)
    assert det.observe(999.0) is False
    srv = GenerationServer(FakeBackend(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        for path in ("/debug/state", "/debug/flight", "/debug/timeseries"):
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=10
                )
            assert exc_info.value.code == 404, path
    finally:
        srv.stop()


def test_request_through_scheduler_yields_span_tree_and_energy(obs_on):
    """The tentpole's end-to-end: one HTTP request through BatchScheduler
    produces a request-rooted queue→prefill→decode span tree and a
    finite J/token estimate on the result."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.client import (
        RemoteHTTPBackend,
    )

    TRACER.clear()
    backend = JaxEngine(registry=_tiny_registry(), dtype=jnp.float32)
    # scheduler="window" pinned: per-request energy attribution (token
    # share of ONE shared decode window) is a window/solo-path feature;
    # continuous sessions retire rows across many slices with varying
    # companions and attach sched latency extras instead.
    srv = GenerationServer(
        backend, host="127.0.0.1", port=0, quiet=True, batch_window_ms=20,
        scheduler="window",
    )
    srv.start()
    try:
        client = RemoteHTTPBackend(f"http://127.0.0.1:{srv.port}")
        result = client.generate(
            GenerationRequest("tiny", "observe me", max_new_tokens=6)
        )
    finally:
        srv.stop()

    # finite per-request energy attribution rode the wire (x_extras)
    energy = (result.extras or {}).get("energy_model")
    assert energy is not None
    assert energy["J_per_token"] > 0
    assert energy["J_low"] < energy["J"] < energy["J_high"]

    spans = TRACER.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    assert "request" in by_name and "queue" in by_name
    root = by_name["request"][0]
    queue = by_name["queue"][0]
    assert queue.parent_id == root.span_id
    # prefill and decode parent under the SAME request root (the
    # scheduler re-attached it on its own thread)
    assert any(s.parent_id == root.span_id for s in by_name["prefill"])
    assert any(s.parent_id == root.span_id for s in by_name["decode"])


def test_paged_pool_and_engine_families_in_exposition(obs_on):
    """Engine + paged-KV gauge families land in the shared registry after
    a paged batched decode (the /metrics surface serves this registry)."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        REGISTRY,
    )

    engine = JaxEngine(
        registry=_tiny_registry(), dtype=jnp.float32, paged_kv=True
    )
    reqs = [
        GenerationRequest("tiny", p, max_new_tokens=5)
        for p in ("one", "two longer prompt", "three")
    ]
    results = engine.generate_batch(reqs)
    assert all(r.generated_tokens for r in results)
    text = REGISTRY.exposition()
    for family in (
        "llm_engine_prefill_seconds",
        "llm_engine_decode_seconds",
        "llm_engine_generated_tokens_total",
        "llm_paged_pool_pages",
        "llm_paged_pool_free_pages",
        "llm_paged_pool_occupancy",
        "llm_request_joules_per_token",
    ):
        assert family in text, family
    # attention-path labels name the paged bf16 path
    assert 'path="paged"' in text and 'kv="bf16"' in text
    # a paged batch row is a session's row: it carries its share of
    # every slice it decoded in, at its own context
    for r in results:
        e = r.extras["energy_model"]
        assert e["window"] == "slice" and e["J"] > 0


def test_scheduler_budget_admission_counter(obs_on):
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        REGISTRY,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        BatchScheduler,
        _Ticket,
    )

    engine = JaxEngine(registry=_tiny_registry(), dtype=jnp.float32)
    sched = BatchScheduler(engine, max_batch=2, budget_aware=True)
    fam = REGISTRY.counter(
        "llm_sched_budget_admission_total", labels=("outcome",)
    )
    before = fam.labels(outcome="raised").value
    cap = sched._admission_cap(
        _Ticket(GenerationRequest("tiny", "budget", max_new_tokens=4))
    )
    assert cap > 2  # tiny config: the KV estimate clears the static cap
    assert fam.labels(outcome="raised").value == before + 1


def test_kill_switch_keeps_serving_but_drops_telemetry(obs_off):
    """Disabled telemetry: requests still serve, zero spans, empty
    registry deltas — the measurement-run guarantee."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )

    TRACER.clear()
    engine = JaxEngine(registry=_tiny_registry(), dtype=jnp.float32)
    result = engine.generate(
        GenerationRequest("tiny", "quiet", max_new_tokens=4)
    )
    assert result.generated_tokens == 4
    assert TRACER.spans() == []
    assert (result.extras or {}).get("energy_model") is None


# -- profiler satellites ------------------------------------------------------


def _run_context(tmp_path):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.context import (
        RunContext,
    )

    run_dir = tmp_path / "run_0"
    run_dir.mkdir()
    return RunContext(
        run_id="run_0",
        run_nr=1,
        total_runs=1,
        variation={},
        run_dir=run_dir,
        experiment_dir=tmp_path,
    )


def test_jax_trace_reports_none_when_start_failed(tmp_path, monkeypatch):
    """Satellite: a failed start_trace must not claim a trace_dir the
    run table would then point at."""
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.jax_trace import (
        JaxTraceProfiler,
    )

    def boom(path):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    prof = JaxTraceProfiler()
    ctx = _run_context(tmp_path)
    prof.on_start(ctx)
    prof.on_stop(ctx)
    assert prof.collect(ctx) == {"trace_dir": None}


def test_jax_trace_reports_dir_when_trace_written(tmp_path, monkeypatch):
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.jax_trace import (
        JaxTraceProfiler,
    )

    monkeypatch.setattr(jax.profiler, "start_trace", lambda path: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    prof = JaxTraceProfiler()
    ctx = _run_context(tmp_path)
    prof.on_start(ctx)
    prof.on_stop(ctx)
    assert prof.collect(ctx)["trace_dir"].endswith("jax_trace")


def test_span_trace_profiler_writes_artifact(obs_on, tmp_path):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.span_trace import (
        SpanTraceProfiler,
    )

    prof = SpanTraceProfiler()
    ctx = _run_context(tmp_path)
    prof.on_start(ctx)
    with TRACER.span("measured-activity"):
        pass
    prof.on_stop(ctx)
    path = prof.collect(ctx)["span_trace"]
    assert path is not None
    events = json.loads((ctx.run_dir / "span_trace.json").read_text())[
        "traceEvents"
    ]
    assert any(e["name"] == "measured-activity" for e in events)


def test_span_trace_profiler_none_without_spans(obs_on, tmp_path):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.span_trace import (
        SpanTraceProfiler,
    )

    prof = SpanTraceProfiler()
    ctx = _run_context(tmp_path)
    prof.on_start(ctx)
    prof.on_stop(ctx)
    assert prof.collect(ctx) == {"span_trace": None}


# -- access log ---------------------------------------------------------------


def test_access_log_opt_in(obs_on, capsys):
    srv = GenerationServer(
        FakeBackend(), host="127.0.0.1", port=0, quiet=True, access_log=True
    )
    srv.start()
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10
        ).read()
    finally:
        srv.stop()
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if '"/healthz"' in l)
    record = json.loads(line.split("serve: ", 1)[1])
    assert record["method"] == "GET" and record["status"] == 200
    assert record["duration_ms"] >= 0


def test_access_log_default_off(obs_on, capsys):
    srv = GenerationServer(FakeBackend(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10
        ).read()
    finally:
        srv.stop()
    assert "/healthz" not in capsys.readouterr().out


# -- bucket quantile estimators (ISSUE 17) ------------------------------------


def test_quantile_from_buckets_monotone_in_q():
    """Property: the estimate is non-decreasing in q for any bucket
    mass (swept over several shapes including +Inf-heavy ones)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        quantile_from_buckets,
    )

    bounds = (0.01, 0.1, 0.5, 2.0)
    shapes = [
        (5, 0, 0, 0, 0),
        (1, 2, 3, 4, 5),
        (0, 0, 0, 0, 7),  # everything overflowed
        (10, 0, 0, 0, 3),
        (1, 1, 1, 1, 1),
    ]
    qs = [i / 20 for i in range(21)]
    for counts in shapes:
        estimates = [quantile_from_buckets(bounds, counts, q) for q in qs]
        assert all(e is not None for e in estimates), counts
        for lo, hi in zip(estimates, estimates[1:]):
            assert lo <= hi, (counts, estimates)


def test_quantile_from_buckets_exact_on_single_bucket_mass():
    """Property: with ALL mass in one finite bucket, every quantile
    interpolates inside that bucket's bounds — and q=1.0 hits its upper
    bound exactly."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        quantile_from_buckets,
    )

    bounds = (0.01, 0.1, 0.5, 2.0)
    for i, (lo, hi) in enumerate(zip((0.0,) + bounds, bounds)):
        counts = [0] * (len(bounds) + 1)
        counts[i] = 9
        for q in (0.01, 0.5, 0.99):
            est = quantile_from_buckets(bounds, counts, q)
            assert lo < est <= hi, (i, q, est)
        assert quantile_from_buckets(bounds, counts, 1.0) == hi
        # linear inside the bucket: q=0.5 is the bucket's midpoint
        assert quantile_from_buckets(bounds, counts, 0.5) == pytest.approx(
            lo + (hi - lo) / 2
        )


def test_quantile_from_buckets_inf_and_edge_handling():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        quantile_from_buckets,
    )

    bounds = (0.1, 1.0)
    # mass only in +Inf clamps to the last finite bound
    assert quantile_from_buckets(bounds, (0, 0, 5), 0.99) == 1.0
    # empty histogram -> None
    assert quantile_from_buckets(bounds, (0, 0, 0), 0.5) is None
    # q outside [0,1] clamps rather than raising
    assert quantile_from_buckets(bounds, (4, 0, 0), -1.0) is not None
    assert quantile_from_buckets(bounds, (4, 0, 0), 2.0) == 0.1
    # counts/bounds length mismatch is a caller bug -> ValueError
    with pytest.raises(ValueError):
        quantile_from_buckets(bounds, (1, 2), 0.5)


def test_bucket_fraction_below_additive_across_merged_histograms():
    """Property: the fraction computed on bucket-wise SUMMED counts
    equals the count-weighted mean of per-histogram fractions — the
    algebra that makes fleet attainment equal the per-replica merge."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        bucket_fraction_below,
    )

    bounds = (0.01, 0.1, 0.5, 2.0)
    a = (30, 5, 0, 0, 0)
    b = (2, 1, 4, 10, 3)
    merged = tuple(x + y for x, y in zip(a, b))
    for threshold in (0.005, 0.01, 0.07, 0.1, 0.3, 2.0, 99.0):
        fa = bucket_fraction_below(bounds, a, threshold)
        fb = bucket_fraction_below(bounds, b, threshold)
        fm = bucket_fraction_below(bounds, merged, threshold)
        weighted = (fa * sum(a) + fb * sum(b)) / (sum(a) + sum(b))
        assert fm == pytest.approx(weighted, abs=1e-12), threshold
    with pytest.raises(ValueError):
        bucket_fraction_below(bounds, a[:-1], 0.1)
    assert bucket_fraction_below(bounds, (0,) * 5, 0.1) is None


# -- windowed telemetry on the served path (ISSUE 17) -------------------------


def test_debug_timeseries_endpoint_after_served_request(obs_on):
    """/debug/timeseries serves windowed rollups (and the SLO snapshot
    when --slo is set) after one request through the scheduler."""
    srv = GenerationServer(
        FakeBackend(),
        host="127.0.0.1",
        port=0,
        quiet=True,
        batch_window_ms=20,
        slo="ttft_p99_ms<=250",
        ts_interval_s=0.05,
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/api/generate",
            data=json.dumps(
                {"model": "m", "prompt": "p", "options": {"num_predict": 4}}
            ).encode(),
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read())["done"]
        # let the sampler take a post-traffic snapshot
        deadline = threading.Event()
        for _ in range(100):
            with urllib.request.urlopen(
                f"{base}/debug/timeseries?family=llm_sched_requests_total",
                timeout=10,
            ) as resp:
                body = json.loads(resp.read())
            rollup = body.get("rollup")
            if rollup and rollup["children"].get("_", {}).get("delta"):
                break
            deadline.wait(0.05)
        assert rollup is not None
        assert rollup["children"]["_"]["delta"] >= 1.0
        assert body["slo"]["objectives"][0]["name"] == "ttft_p99_ms"
        # bad ?window= is a 400, not a 500
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"{base}/debug/timeseries?window=bogus", timeout=10
            )
        assert exc_info.value.code == 400
        # the sampled queue-depth gauge exists on the served path
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            assert "llm_sched_queue_depth" in resp.read().decode()
    finally:
        srv.stop()
    assert not srv._sampler.running


def test_kill_switch_keeps_sampler_and_slo_engine_off(obs_off):
    """ISSUE 17 kill-switch completeness: with telemetry off the
    sampler thread never starts, SLO evaluation is a no-op, and the
    ring stays empty — even when --slo was configured."""
    srv = GenerationServer(
        FakeBackend(),
        host="127.0.0.1",
        port=0,
        quiet=True,
        slo="ttft_p99_ms<=250",
        ts_interval_s=0.05,
    )
    srv.start()
    try:
        assert not srv._sampler.running
        assert len(srv.ts_ring) == 0
        assert srv.slo_engine is not None
        assert srv.slo_engine.evaluate() is None
    finally:
        srv.stop()


# -- phase spans of the serving loop, request roots, profiler annotations ------
# (ISSUE 25: one span call, two sinks; PERF.md §3 has the span -> metric table)

PHASES = ("queue", "join.wait", "join.prefill", "join.commit", "egress.first")
SCHED_PHASES = {
    "sched.reap", "sched.slice", "sched.egress", "sched.join",
    "sched.admit", "sched.sweep",
}


def _drain_channel(chan, timeout_s=20.0):
    """(first delta's arrival on time.monotonic, final result)."""
    import time

    t_first, result = None, None
    for event in chan.events(timeout_s=timeout_s):
        if event.kind == "delta" and t_first is None:
            t_first = time.monotonic()
        elif event.kind == "done":
            result = event.result
        elif event.kind == "error":
            raise event.error
    return t_first, result


def _continuous(backend=None, **kw):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )

    backend = backend or FakeBackend(tokens_per_s=400.0, simulate_delay=True)
    sched = ContinuousScheduler(backend, slice_steps=8, **kw)
    sched.start()
    return sched


def _served_with_a_joiner(prefill_chunk_tokens=16):
    """An anchor opens a session; a second request joins it mid-flight,
    submitted from this thread with no span open. Returns the joiner's
    (result, spans recorded since the start)."""
    mark = TRACER.seq()
    assert TRACER.current() is None
    sched = _continuous(prefill_chunk_tokens=prefill_chunk_tokens)
    try:
        anchor = sched.submit_stream(
            GenerationRequest("m", "anchor " * 4, max_new_tokens=160, seed=1)
        )
        first = next(iter(anchor.events(timeout_s=10.0)))
        assert first.kind == "delta"  # the session is open and decoding
        joiner = sched.submit_stream(
            GenerationRequest("m", "j" * 40, max_new_tokens=24, seed=2)
        )
        _, result = _drain_channel(joiner)
        anchor.cancel()
    finally:
        sched.stop()
    return result, TRACER.spans(since=mark)


def test_request_root_at_submit_and_ttft_split(obs_on):
    result, spans = _served_with_a_joiner()
    assert result.extras["sched"]["joined"] is True
    assert result.extras["sched"]["join_chunks"] >= 2  # 41 tokens, 16 a chunk
    ttft_s = result.extras["sched"]["ttft_s"]
    # one request root per submitted request, none left on this thread's stack
    assert TRACER.current() is None
    roots = [s for s in spans if s.name == "request" and s.parent_id is None]
    joiner_root = next(r for r in roots if r.attrs.get("model") == "m" and abs(r.dur_s - result.extras["sched"]["completion_s"]) < 5e-3)
    assert joiner_root.trace_id and len(joiner_root.trace_id) == 16
    assert len({r.trace_id for r in roots}) == len(roots) == 2
    mine = [s for s in spans if s.parent_id == joiner_root.span_id and s.name in PHASES]
    assert {s.name for s in mine} == set(PHASES)
    assert all(s.trace_id == joiner_root.trace_id for s in mine)
    # the phases tile submit -> first push: no gap, no overlap, sum = TTFT
    mine.sort(key=lambda s: s.t0_s)
    assert mine[0].name == "queue" and mine[-1].name == "egress.first"
    assert mine[0].t0_s == pytest.approx(joiner_root.t0_s, abs=1e-3)
    for a, b in zip(mine, mine[1:]):
        assert b.t0_s == pytest.approx(a.t0_s + a.dur_s, abs=1e-9)
    assert sum(s.dur_s for s in mine) == pytest.approx(ttft_s, abs=1e-3)
    assert sum(1 for s in mine if s.name == "join.prefill") == result.extras["sched"]["join_chunks"]
    assert sum(1 for s in mine if s.name == "join.commit") == 1
    # the live session spans of its join ran under its root too (attach) and share the trace
    prefills = [s for s in spans if s.name == "session.join.prefill" and s.trace_id == joiner_root.trace_id]
    assert len(prefills) == result.extras["sched"]["join_chunks"]


def test_request_that_opens_a_session_has_queue_then_open(obs_on):
    mark = TRACER.seq()
    sched = _continuous()
    try:
        chan = sched.submit_stream(GenerationRequest("m", "alone", max_new_tokens=12, seed=3))
        _, result = _drain_channel(chan)
    finally:
        sched.stop()
    spans = TRACER.spans(since=mark)
    root = next(s for s in spans if s.name == "request")
    kids = sorted((s for s in spans if s.parent_id == root.span_id), key=lambda s: s.t0_s)
    assert [s.name for s in kids][:2] == ["queue", "open"]
    assert kids[0].dur_s + kids[1].dur_s == pytest.approx(result.extras["sched"]["ttft_s"], abs=1e-3)
    assert any(s.name == "sched.open" for s in spans)


def test_an_http_root_still_wins_over_the_tickets_own(obs_on):
    mark = TRACER.seq()
    sched = _continuous()
    try:
        with TRACER.span("request", trace_id="feedfacefeedface") as http_root:
            result = sched.submit(GenerationRequest("m", "rooted", max_new_tokens=6))
        assert result.generated_tokens == 6
    finally:
        sched.stop()
    spans = TRACER.spans(since=mark)
    assert [s for s in spans if s.name == "request"] == [http_root]
    queue = next(s for s in spans if s.name == "queue")
    assert queue.parent_id == http_root.span_id and queue.trace_id == "feedfacefeedface"


def test_a_failed_ticket_closes_its_root(obs_on):
    mark = TRACER.seq()
    backend = FakeBackend()
    backend.fail_decode_open = True
    sched = _continuous(backend)
    try:
        chan = sched.submit_stream(GenerationRequest("m", "doomed", max_new_tokens=4))
        with pytest.raises(Exception):
            _drain_channel(chan)
    finally:
        sched.stop()
    roots = [s for s in TRACER.spans(since=mark) if s.name == "request"]
    assert len(roots) == 1 and roots[0].dur_s is not None


def test_one_pass_of_the_loop_is_a_sched_iter_with_its_phases_nested(obs_on):
    _, spans = _served_with_a_joiner()
    iters = [s for s in spans if s.name == "sched.iter"]
    assert len(iters) >= 3
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.parent_id, []).append(s)
    saw = set()
    for it in iters:
        assert {"rows", "pending", "queued"} <= set(it.attrs)
        kids = sorted(by_parent.get(it.span_id, []), key=lambda s: s.t0_s)
        assert kids and {k.name for k in kids} <= SCHED_PHASES
        saw |= {k.name for k in kids}
        assert kids[0].t0_s >= it.t0_s and kids[-1].t0_s + kids[-1].dur_s <= it.t0_s + it.dur_s + 1e-9
        for a, b in zip(kids, kids[1:]):
            assert a.t0_s + a.dur_s <= b.t0_s + 1e-9  # phases do not overlap
        for k in kids:
            if k.name == "sched.slice":
                assert k.attrs["rows"] >= 1 and k.attrs["retired"] >= 0 and k.attrs["ctx_tokens"] > 0
                inner = [s.name for s in sorted(by_parent.get(k.span_id, []), key=lambda s: s.t0_s)]
                assert inner == ["session.slice.wait", "session.slice.account"]  # the fake's two
    assert saw == SCHED_PHASES
    # the session's join spans lie inside sched.join / sched.admit in time, on the loop's thread
    for name, outer in (("session.join.prefill", "sched.join"), ("session.join.commit", "sched.join"),
                        ("session.join.begin", "sched.admit")):
        inner = [s for s in spans if s.name == name]
        assert inner
        for s in inner:
            assert any(o.name == outer and o.tid == s.tid and o.t0_s <= s.t0_s
                       and s.t0_s + s.dur_s <= o.t0_s + o.dur_s + 1e-9 for o in spans)


class _FakeAnnotation:
    entered = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.entered.append((self.name, self.kw))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.entered.append(("exit", self.name))


def test_span_enters_a_trace_annotation_only_when_jax_is_imported(obs_on, monkeypatch):
    import sys
    import types

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import trace as obs_trace

    tracer = SpanTracer()
    fake_jax = types.SimpleNamespace(profiler=types.SimpleNamespace(TraceAnnotation=_FakeAnnotation))
    _FakeAnnotation.entered = []
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    with tracer.span("sched.iter", rows=3):
        with tracer.span("sched.slice"):
            pass
    assert _FakeAnnotation.entered == [
        ("sched.iter", {"rows": 3}), ("sched.slice", {}), ("exit", "sched.slice"), ("exit", "sched.iter"),
    ]
    # ring-only kinds reach no annotation: a timed interval, a detached root
    _FakeAnnotation.entered = []
    root = tracer.root("request", trace_id="t" * 16)
    tracer.add_span("queue", 0.0, 1.0, parent=root)
    tracer.finish(root)
    tracer.finish(root)  # idempotent
    assert _FakeAnnotation.entered == [] and tracer.current() is None
    assert [s.name for s in tracer.spans()][-2:] == ["queue", "request"]
    # a process that never imported jax looks nothing up and enters nothing
    monkeypatch.delitem(sys.modules, "jax")
    assert obs_trace._annotation("sched.iter", {}) is None
    with tracer.span("sched.iter"):
        pass
    assert _FakeAnnotation.entered == []


def test_span_lies_on_the_profilers_host_plane(obs_on, tmp_path):
    """The real thing on the CPU: a span open during a profiler session is
    an event of the same name on /host:CPU, its attrs the event's stats."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with TRACER.span("sched.iter", rows=2, pending=1):
        with TRACER.span("session.slice.wait"):
            jax.block_until_ready(jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8)))
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("sched.iter", "session.slice.wait"):
                        events[e.name] = (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
    assert set(events) == {"sched.iter", "session.slice.wait"}
    assert events["sched.iter"][2] == {"rows": 2, "pending": 1}
    assert events["sched.iter"][0] <= events["session.slice.wait"][0]
    assert events["session.slice.wait"][1] <= events["sched.iter"][1]


def test_kill_switch_leaves_no_span_and_no_annotation(obs_off, monkeypatch):
    import sys
    import types

    _FakeAnnotation.entered = []
    monkeypatch.setitem(
        sys.modules, "jax",
        types.SimpleNamespace(profiler=types.SimpleNamespace(TraceAnnotation=_FakeAnnotation)),
    )
    mark = TRACER.seq()
    sched = _continuous(FakeBackend())
    try:
        chan = sched.submit_stream(GenerationRequest("m", "quiet", max_new_tokens=20))
        _, result = _drain_channel(chan)
        assert result.generated_tokens == 20
    finally:
        sched.stop()
    assert TRACER.spans(since=mark) == [] and _FakeAnnotation.entered == []
    assert TRACER.root("request") is None


def test_real_stepped_session_writes_the_session_spans(obs_on):
    """The real SteppedDecodeSession at a tiny size on the CPU: a slice has
    its four phases in order under one parent; a chunked join has begin,
    prefill and commit, with the row install inside the commit."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )

    engine = JaxEngine(registry=_tiny_registry(), dtype=jnp.float32, paged_kv=True)
    a = GenerationRequest("tiny", "the quick brown fox", max_new_tokens=12, seed=1)
    b = GenerationRequest("tiny", "jumps over", max_new_tokens=6, seed=2)
    session = engine.decode_open([a], reserve_rows=4, slice_steps=4)
    try:
        mark = TRACER.seq()
        with TRACER.span("sched.slice") as outer:
            session.step(4)
        names = [s.name for s in sorted(TRACER.spans(since=mark), key=lambda s: s.t0_s)
                 if s.parent_id == outer.span_id]
        assert names == ["session.slice.dispatch", "session.slice.wait", "session.slice.fetch",
                         "session.slice.account"]
        assert session.ctx_tokens == len(session.tok.encode(a.prompt)) + 1 + 4
        mark = TRACER.seq()
        pj = session.join_begin(b, chunk_tokens=16)
        while not session.join_step(pj):
            pass
        session.join_commit(pj)
        spans = TRACER.spans(since=mark)
        got = [s.name for s in sorted(spans, key=lambda s: s.t0_s) if s.name.startswith("session.join.")]
        assert got == ["session.join.begin", "session.join.prefill", "session.join.commit",
                       "session.join.install"]
        commit = next(s for s in spans if s.name == "session.join.commit")
        install = next(s for s in spans if s.name == "session.join.install")
        assert install.parent_id == commit.span_id
        assert session.active == 2
    finally:
        session.close()


def test_sched_slice_carries_the_pool_and_the_pages_rows_hold_fake_twin(obs_on):
    """``sched.slice``'s ``pool_pages`` / ``pool_pages_owned`` (PERF.md §3):
    the share of the pool a step reads that somebody needed. The fake
    twin: a pool of FAKE_ROW_PAGES a row slot, the live rows' prompt pages."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FAKE_PREFIX_PAGE,
        FAKE_ROW_PAGES,
    )

    _, spans = _served_with_a_joiner()
    slices = sorted((s for s in spans if s.name == "sched.slice"), key=lambda s: s.t0_s)
    assert slices and {s.attrs["pool_pages"] for s in slices} == {64 * FAKE_ROW_PAGES}
    anchor_pages = -(-(len("anchor " * 4) + 1) // FAKE_PREFIX_PAGE)
    joiner_pages = -(-(40 + 1) // FAKE_PREFIX_PAGE)
    assert slices[0].attrs["pool_pages_owned"] == anchor_pages
    assert {s.attrs["pool_pages_owned"] for s in slices} == {anchor_pages, anchor_pages + joiner_pages}
    assert all(s.attrs["rows"] == (1 if s.attrs["pool_pages_owned"] == anchor_pages else 2) for s in slices)


def test_sched_slice_carries_the_pool_and_the_pages_rows_hold_real_session(obs_on):
    """The same two attributes from the real session, a stacked paged one
    on the CPU, through the scheduler; the session's ``/debug/state`` names
    the attention its step compiled: the XLA parts path over the pool in place."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    engine = JaxEngine(registry=_tiny_registry(), dtype=jnp.float32, paged_kv=True,
                       decode_attention=pallas_decode_attention)
    request = GenerationRequest("tiny", "a" * 200, max_new_tokens=24, stop_at_eos=False)
    session = engine.decode_open([request], reserve_rows=4)
    try:  # what the scheduler's /debug/state forwards for its live session
        state = session.debug_state()
    finally:
        session.close()
    assert state["attention"] == {"table_width": 4, "impl": "xla-pool"}
    mark = TRACER.seq()
    sched = _continuous(engine)
    try:
        _drain_channel(sched.submit_stream(request), timeout_s=60.0)
    finally:
        sched.stop()
    slices = [s for s in TRACER.spans(since=mark) if s.name == "sched.slice"]
    assert slices
    # 201 prompt tokens: two pages of 128 on the one live row, of a pool that holds them twice over and a parking page
    assert {(s.attrs["pool_pages"], s.attrs["pool_pages_owned"]) for s in slices} == {(state["pool"]["pages"], 2)}
    assert state["pool"]["pages"] == 8
