"""The LLM-energy study config, run hermetically on the fake backend."""

import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import FakeBackend
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
    LlmEnergyConfig,
    MODELS,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.topics import (
    TOPICS,
    pick_topic,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
    TpuEnergyModelProfiler,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.context import RunContext
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.controller import (
    ExperimentController,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.persistence import (
    RunTableStore,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.progress import RunProgress


def test_topics_pool_and_seeded_pick():
    assert len(TOPICS) >= 100
    assert len(set(TOPICS)) == len(TOPICS)
    assert pick_topic(seed=42) == pick_topic(seed=42)
    assert any(pick_topic(seed=i) != pick_topic(seed=0) for i in range(1, 10))


def test_default_sweep_shape():
    config = LlmEnergyConfig()
    model = config.create_run_table_model()
    # 7 models × 2 locations × 3 lengths (experiment/RunnerConfig.py:80-88)
    assert len(model.variations()) == 7 * 2 * 3
    assert len(MODELS) == 7
    # Cooldown is channel-typed: the reference's 90 s thermal discipline
    # (RunnerConfig.py:55) when any measured energy channel is active,
    # 2 s when every energy column is modelled (thermal-state-free).
    expect = (
        LlmEnergyConfig.MEASURED_CHANNEL_COOLDOWN_MS
        if any(getattr(p, "measured_channel", False) for p in config.profilers)
        else LlmEnergyConfig.MODELLED_ONLY_COOLDOWN_MS
    )
    assert config.time_between_runs_in_ms == expect


def test_cooldown_policy_follows_channel_type(monkeypatch):
    """Explicit cooldown always wins; otherwise a measured channel re-grows
    the reference's 90 s thermal discipline (VERDICT round-2 item 9)."""
    config = LlmEnergyConfig(cooldown_ms=1234)
    assert config.time_between_runs_in_ms == 1234

    # A measured channel present at construction → the reference's 90 s.
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers import (
        native_host,
    )

    monkeypatch.setattr(
        native_host.NativeHostProfiler,
        "measured_channel",
        property(lambda self: True),
    )
    config = LlmEnergyConfig()
    assert (
        config.time_between_runs_in_ms
        == LlmEnergyConfig.MEASURED_CHANNEL_COOLDOWN_MS
    )


def test_energy_model_profiler_math(tmp_path):
    prof = TpuEnergyModelProfiler(
        peak_tflops=100.0, peak_w=200.0, idle_w=50.0, mxu_active_w=150.0
    )
    ctx = RunContext("r", 1, 1, {}, tmp_path, tmp_path)
    ctx.scratch["generation_stats"] = {
        "flops": 50.0e12,  # half of peak over 1 s → util 0.5
        "duration_s": 1.0,
        "generated_tokens": 100,
    }
    prof.on_start(ctx)
    prof.on_stop(ctx)
    data = prof.collect(ctx)
    # 50 W idle + 0.5 MXU duty × 150 W engine coefficient = 125 J over 1 s
    assert data["energy_model_J"] == pytest.approx(125.0)
    assert data["joules_per_token"] == pytest.approx(1.25)
    assert data["tpu_util_est"] == 0.5
    assert data["tpu_power_model_W"] == pytest.approx(125.0)


def test_energy_model_profiler_without_stats(tmp_path):
    prof = TpuEnergyModelProfiler()
    ctx = RunContext("r", 1, 1, {}, tmp_path, tmp_path)
    prof.on_start(ctx)
    prof.on_stop(ctx)
    assert prof.collect(ctx)["energy_model_J"] is None


def test_energy_window_excludes_transport_time(tmp_path):
    """Modelled energy's idle-power window is the fence-timed DECODE loop
    (the serving side's own clock), not the request wall time — HTTP and
    host-dispatch jitter (both ``total_s`` and the dispatch-dominated
    ``prefill_s`` of short prompts) must not leak into Joules; prefill is
    charged through the FLOPs term (VERDICT round-2 item 1)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationResult,
    )

    class TransportyBackend(FakeBackend):
        def generate(self, request):
            r = super().generate(request)
            return GenerationResult(
                request=r.request,
                tokens=r.tokens,
                text=r.text,
                prompt_tokens=r.prompt_tokens,
                generated_tokens=r.generated_tokens,
                prefill_s=0.01,
                decode_s=0.5,
                total_s=3.0,  # ~2.5 s of wire/transport time
            )

    be = TransportyBackend()
    config = LlmEnergyConfig(
        models=["qwen2:1.5b"],
        locations=["on_device"],
        lengths=[100],
        repetitions=1,
        cooldown_ms=0,
        backends={"on_device": be},
        results_output_path=tmp_path,
    )
    ctx = RunContext(
        "run_0_repetition_0",
        1,
        1,
        {"model": "qwen2:1.5b", "location": "on_device", "length": 100},
        tmp_path,
        tmp_path,
    )
    config.start_run(ctx)
    config.interact(ctx)
    stats = ctx.scratch["generation_stats"]
    assert stats["duration_s"] == pytest.approx(0.5)  # decode_s only
    # flops cover ALL processed tokens — prefill's compute is charged
    # through the FLOPs term, not a dispatch-dominated wall window
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        MODEL_REGISTRY,
    )

    cfg = MODEL_REGISTRY["qwen2:1.5b"]
    r = ctx.scratch["result"]
    total = r.prompt_tokens + r.generated_tokens
    assert stats["flops"] == pytest.approx(cfg.flops_per_token(total) * total)
    # and execution_time_s (the reference's client-observed wall time)
    # still records the full request duration
    data = config.populate_run_data(ctx)
    assert data["execution_time_s"] == pytest.approx(3.0)


def test_recompute_energy_reproduces_modelled_columns(tmp_path):
    """Modelled energy is a pure function of persisted raw measurements:
    recomputing an existing table under the current model reproduces the
    live-run values exactly (and lets a model refinement be applied
    post-hoc, like the reference's derived J column)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    config = _hermetic_config(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    exp = tmp_path / "llm_energy_tpu"
    before = {
        r["__run_id"]: r["energy_model_J"] for r in RunTableStore(exp).read()
    }
    assert any(v is not None for v in before.values())
    n = recompute_energy(exp, reanalyze=False)
    after = {
        r["__run_id"]: r["energy_model_J"] for r in RunTableStore(exp).read()
    }
    assert n == len(before)
    for rid, v in before.items():
        assert after[rid] == pytest.approx(v, rel=1e-6), rid


def _hermetic_config(tmp_path, **kw):
    # simulate_delay gives each run a real ~30 ms measurement window so the
    # sampling profilers observe a nonzero span.
    fake = FakeBackend(tokens_per_s=5000.0, simulate_delay=True)
    return LlmEnergyConfig(
        models=["qwen2:1.5b", "gemma:2b"],
        locations=["on_device", "remote"],
        lengths=[100],
        repetitions=2,
        results_output_path=tmp_path,
        cooldown_ms=0,
        backends={"on_device": fake, "remote": fake},
        shuffle=True,
        **kw,
    )


def test_full_study_lifecycle_on_fake_backend(tmp_path):
    config = _hermetic_config(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    rows = RunTableStore(tmp_path / "llm_energy_tpu").read()
    assert len(rows) == 2 * 2 * 1 * 2
    assert all(r["__done"] == RunProgress.DONE for r in rows)
    for row in rows:
        assert row["topic"] in TOPICS
        assert row["generated_tokens"] == 134  # ceil(100 * 4/3)
        assert row["execution_time_s"] > 0
        assert row["tokens_per_s"] > 0
        assert row["cpu_usage"] is not None  # host profiler columns present
    # analysis report written by after_experiment
    assert (tmp_path / "llm_energy_tpu" / "analysis_report.json").exists()


def test_study_resume_reuses_topic(tmp_path):
    config = _hermetic_config(tmp_path)
    ctrl = ExperimentController(config, echo=False)
    first_id = ctrl.rows[0]["__run_id"]
    ctrl.do_experiment()
    stored = {r["__run_id"]: r["topic"] for r in ctrl.store.read()}
    # same run id → same seeded topic on a fresh config instance
    import zlib

    config2 = _hermetic_config(tmp_path)
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.topics import (
        pick_topic as pick,
    )

    topic2 = pick(seed=zlib.crc32(f"{config2.seed}|{first_id}".encode()))
    assert stored[first_id] == topic2


def test_remote_runs_do_not_poison_on_device_chip_count(tmp_path):
    """Regression (found in a real TPU capstone run): the shared energy
    profiler's n_chips is mutated per run; when the target count was read
    back from an aliased profiler instance, one remote run (8 chips)
    permanently poisoned every later on_device run. before_run must set
    the count from plain config data."""
    cfg = LlmEnergyConfig(
        models=["m"],
        lengths=[100],
        repetitions=1,
        cooldown_ms=0,
        results_output_path=tmp_path,
        backends={"on_device": FakeBackend(), "remote": FakeBackend()},
    )

    def ctx(location):
        return RunContext(
            run_id="r",
            run_nr=1,
            total_runs=2,
            variation={"model": "m", "location": location, "length": 100},
            run_dir=tmp_path,
            experiment_dir=tmp_path,
        )

    idx = cfg._model_profiler_index()
    cfg.before_run(ctx("remote"))
    assert cfg.profilers[idx].n_chips == 8
    cfg.before_run(ctx("on_device"))
    assert cfg.profilers[idx].n_chips == 1  # failed when read from the alias
    cfg.before_run(ctx("remote"))
    assert cfg.profilers[idx].n_chips == 8


def test_backend_column_recorded_per_run(tmp_path):
    config = _hermetic_config(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    rows = RunTableStore(tmp_path / "llm_energy_tpu").read()
    assert all(row["backend"] for row in rows)
    # both treatments are served by the same FakeBackend object → remote
    # rows must be flagged as aliased so nobody mistakes them for a real
    # machine boundary
    for row in rows:
        if row["location"] == "remote":
            assert "aliased-on_device" in row["backend"]
        else:
            assert "aliased" not in row["backend"]


def test_describe_backend_for_http_and_engine():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.client import (
        RemoteHTTPBackend,
    )

    cfg = LlmEnergyConfig(
        models=["m"],
        lengths=[100],
        repetitions=1,
        backends={
            "on_device": FakeBackend(),
            "remote": RemoteHTTPBackend("http://10.0.0.5:11434"),
        },
    )
    assert cfg.describe_backend("on_device") == "FakeBackend[1chip]"
    assert cfg.describe_backend("remote") == "http:http://10.0.0.5:11434"


def test_energy_channels_report_written(tmp_path):
    config = _hermetic_config(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    import json

    path = tmp_path / "llm_energy_tpu" / "energy_channels.json"
    assert path.exists()
    payload = json.loads(path.read_text())
    assert {c["name"] for c in payload["channels"]} >= {"rapl", "hwmon"}


def test_on_device_url_builds_http_backend_and_checks_health(tmp_path):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend as FB,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.server import (
        GenerationServer,
    )

    srv = GenerationServer(FB(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        cfg = LlmEnergyConfig(
            models=["m"],
            lengths=[100],
            repetitions=1,
            results_output_path=tmp_path,
            on_device_url=url,
            remote_url=url,
        )
        cfg.experiment_path = tmp_path / "exp"
        cfg.before_experiment()
        assert cfg.describe_backend("on_device") == f"http:{url}"
        # same URL for both treatments → one serving process, one chip:
        # the remote rows are aliased and must say so (the round-3
        # capstone recorded identical URLs unmarked, hiding that its
        # remote timings were single-chip; VERDICT round-3 missing #3)
        assert (
            cfg.describe_backend("remote")
            == f"http:{url}[aliased-on_device]"
        )

        # a genuinely distinct remote server keeps its own identity
        srv2 = GenerationServer(FB(), host="127.0.0.1", port=0, quiet=True)
        srv2.start()
        try:
            url2 = f"http://127.0.0.1:{srv2.port}"
            cfg2 = LlmEnergyConfig(
                models=["m"],
                lengths=[100],
                repetitions=1,
                results_output_path=tmp_path,
                on_device_url=url,
                remote_url=url2,
            )
            cfg2.experiment_path = tmp_path / "exp2"
            cfg2.before_experiment()
            assert cfg2.describe_backend("remote") == f"http:{url2}"
        finally:
            srv2.stop()
    finally:
        srv.stop()


def test_on_device_url_unreachable_fails_fast(tmp_path):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.runner.errors import (
        ExperimentError,
    )

    cfg = LlmEnergyConfig(
        models=["m"],
        lengths=[100],
        repetitions=1,
        results_output_path=tmp_path,
        on_device_url="http://127.0.0.1:9",  # discard port: nothing listens
    )
    cfg.experiment_path = tmp_path / "exp"
    with pytest.raises(ExperimentError, match="unreachable"):
        cfg.before_experiment()


def test_aliased_remote_rows_get_modeled_mesh_duration(tmp_path):
    """Single-chip hosts serve the remote treatment from the aliased
    on-device backend; billing the 8-chip mesh for the single chip's wall
    time made remote '8× power for identical time' — the opposite of the
    reference's remote-is-faster finding (VERDICT round-3 missing #3).
    Aliased remote rows must carry the TP-roofline modelled window in
    ``remote_modeled_decode_s``, bill energy on it, and keep the raw
    measured ``decode_s`` untouched."""
    config = _hermetic_config(tmp_path)
    ExperimentController(config, echo=False).do_experiment()
    rows = RunTableStore(tmp_path / "llm_energy_tpu").read()
    on_device = [r for r in rows if r["location"] == "on_device"]
    remote = [r for r in rows if r["location"] == "remote"]
    assert all(r["remote_modeled_decode_s"] is None for r in on_device)
    assert all(r["quantize"] == "int8" for r in rows)
    for r in remote:
        assert "[aliased-on_device]" in r["backend"]
        assert r["remote_modeled_decode_s"] is not None
        # the mesh window is modelled, not the measured single-chip time
        assert r["remote_modeled_decode_s"] != r["decode_s"]
        # energy was billed on the modelled window: 8 chips × the
        # modelled duration bounds it from above at peak power
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
            V5E_IDLE_W,
            V5E_PEAK_W,
        )

        lo = 8 * V5E_IDLE_W * r["remote_modeled_decode_s"]
        hi = 8 * V5E_PEAK_W * r["remote_modeled_decode_s"]
        assert lo * 0.99 <= r["energy_model_J"] <= hi * 1.01


def test_recompute_energy_fallback_aliasing_for_legacy_tables(tmp_path):
    """Tables from before the backend/quantize columns: a remote row with
    chips>1 could only have come from an aliased single-chip run, so
    recompute applies the mesh-duration model to it (and int8, the study
    default, for bytes)."""
    import csv

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    exp = tmp_path / "legacy"
    exp.mkdir()
    cols = [
        "__run_id", "__done", "model", "location", "length", "chips",
        "prompt_tokens", "generated_tokens", "execution_time_s",
        "prefill_s", "decode_s", "tokens_per_s", "energy_model_J",
        "joules_per_token", "tpu_util_est",
    ]
    with (exp / "run_table.csv").open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        for i, (loc, chips) in enumerate(
            [("on_device", 1), ("remote", 8)] * 2
        ):
            w.writerow({
                "__run_id": f"run_{i}_repetition_0", "__done": "DONE",
                "model": "qwen2:1.5b", "location": loc, "length": 100,
                "chips": chips, "prompt_tokens": 64,
                "generated_tokens": 134, "execution_time_s": 0.6,
                "prefill_s": 0.1, "decode_s": 0.45, "tokens_per_s": 297.8,
                "energy_model_J": "", "joules_per_token": "",
                "tpu_util_est": "",
            })
    n = recompute_energy(exp, reanalyze=False)
    assert n == 4
    rows = RunTableStore(exp).read()
    by_loc = {}
    for r in rows:
        by_loc.setdefault(r["location"], []).append(r)
    for r in by_loc["on_device"]:
        assert r["remote_modeled_decode_s"] is None
        # bandwidth duty, not FLOPs duty: util is a real working fraction
        assert r["tpu_util_est"] > 0.3
    for r in by_loc["remote"]:
        assert r["remote_modeled_decode_s"] is not None
        assert r["remote_modeled_decode_s"] < r["decode_s"]  # mesh is faster


def test_generation_stats_bill_replicated_kv_per_chip():
    """sharding.py replicates the KV cache when n_kv_heads % tp != 0:
    every mesh chip then streams the FULL cache, so the mesh's total
    bytes are W + n·KV, not W + KV (code-review round-4 finding). phi3's
    32 heads shard cleanly → no multiplier."""
    import types

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        generation_stats_from,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        decode_kv_stream_bytes,
        decode_weight_stream_bytes,
    )

    result = types.SimpleNamespace(
        prompt_tokens=64, generated_tokens=200, decode_s=0.6, total_s=0.7
    )
    mid = 64 + 100
    qwen = get_model_config("qwen2:1.5b")  # 2 KV heads: 2 % 8 != 0
    s1 = generation_stats_from(qwen, result, quantize="int8", n_chips=1)
    s8 = generation_stats_from(
        qwen, result, quantize="int8", n_chips=8, aliased=True
    )
    kv = decode_kv_stream_bytes(qwen, mid) * 200
    w = decode_weight_stream_bytes(qwen, "int8") * 200
    assert s1["bytes"] == pytest.approx(w + kv)
    assert s8["bytes"] == pytest.approx(w + 8 * kv)

    phi3 = get_model_config("phi3:3.8b")  # 32 % 8 == 0 → sharded
    p8 = generation_stats_from(
        phi3, result, quantize="int8", n_chips=8, aliased=True
    )
    assert p8["bytes"] == pytest.approx(
        (decode_weight_stream_bytes(phi3, "int8")
         + decode_kv_stream_bytes(phi3, mid)) * 200
    )


def test_generation_stats_unknown_model_warns_on_aliased_mesh(capsys):
    """A model missing from the registry cannot be mesh-modelled: the
    aliased remote row keeps the measured window and the study says so
    out loud instead of silently reverting to idle-billing (code-review
    round-4 finding)."""
    import types

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        generation_stats_from,
    )

    result = types.SimpleNamespace(
        prompt_tokens=10, generated_tokens=20, decode_s=0.1, total_s=0.2,
        request=types.SimpleNamespace(model="mystery:13b"),
    )
    stats = generation_stats_from(
        None, result, quantize="int8", n_chips=8, aliased=True
    )
    assert "bytes" not in stats and "modeled_decode_s" not in stats
    err = capsys.readouterr()
    assert "mystery:13b" in err.out + err.err


def test_aliased_detection_canonicalizes_urls(tmp_path):
    """localhost and 127.0.0.1 (and a trailing slash) are one server —
    one chip. Equivalent spellings must still be detected as aliasing
    (code-review round-4 finding)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend as FB,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.server import (
        GenerationServer,
    )

    srv = GenerationServer(FB(), host="127.0.0.1", port=0, quiet=True)
    srv.start()
    try:
        cfg = LlmEnergyConfig(
            models=["m"],
            lengths=[100],
            repetitions=1,
            results_output_path=tmp_path,
            on_device_url=f"http://127.0.0.1:{srv.port}",
            remote_url=f"http://localhost:{srv.port}/",
        )
        cfg.experiment_path = tmp_path / "exp"
        cfg.before_experiment()
        assert cfg.describe_backend("remote").endswith("[aliased-on_device]")
    finally:
        srv.stop()


def test_recompute_energy_skips_rows_missing_raw_inputs(tmp_path):
    """A legacy table with a hole in ANY raw input column skips that row
    instead of aborting the whole recompute (code-review round-4
    finding)."""
    import csv

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    exp = tmp_path / "holes"
    exp.mkdir()
    cols = [
        "__run_id", "__done", "model", "location", "length",
        "prompt_tokens", "generated_tokens", "execution_time_s",
        "decode_s",
    ]
    with (exp / "run_table.csv").open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        base = {
            "__done": "DONE", "model": "qwen2:1.5b",
            "location": "on_device", "length": 100,
            "prompt_tokens": 64, "generated_tokens": 134,
            "execution_time_s": 0.6, "decode_s": 0.45,
        }
        w.writerow({**base, "__run_id": "run_0_repetition_0"})
        w.writerow(
            {**base, "__run_id": "run_1_repetition_0", "prompt_tokens": ""}
        )
        w.writerow(
            {**base, "__run_id": "run_2_repetition_0",
             "execution_time_s": ""}
        )
    assert recompute_energy(exp, reanalyze=False) == 1


def test_recompute_energy_warning_names_the_model(tmp_path, capsys):
    import csv

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    exp = tmp_path / "unknown"
    exp.mkdir()
    cols = [
        "__run_id", "__done", "model", "location", "length", "chips",
        "prompt_tokens", "generated_tokens", "execution_time_s", "decode_s",
    ]
    with (exp / "run_table.csv").open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerow({
            "__run_id": "run_0_repetition_0", "__done": "DONE",
            "model": "mystery:13b", "location": "remote", "length": 100,
            "chips": 8, "prompt_tokens": 64, "generated_tokens": 134,
            "execution_time_s": 0.6, "decode_s": 0.45,
        })
    recompute_energy(exp, reanalyze=False)
    out = capsys.readouterr()
    assert "mystery:13b" in out.out + out.err


def test_recompute_cross_row_aliasing_canonicalizes_backend_urls(tmp_path):
    """A legacy table recorded with localhost for one treatment and
    127.0.0.1 for the other (one loopback server) must still be detected
    as aliased by recompute (code-review round-4 finding)."""
    import csv

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    exp = tmp_path / "spellings"
    exp.mkdir()
    cols = [
        "__run_id", "__done", "model", "location", "length", "backend",
        "chips", "prompt_tokens", "generated_tokens",
        "execution_time_s", "decode_s",
    ]
    with (exp / "run_table.csv").open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        for i, (loc, url, chips) in enumerate([
            ("on_device", "http:http://127.0.0.1:11434", 1),
            ("remote", "http:http://localhost:11434/", 8),
        ]):
            w.writerow({
                "__run_id": f"run_{i}_repetition_0", "__done": "DONE",
                "model": "qwen2:1.5b", "location": loc, "length": 100,
                "backend": url, "chips": chips, "prompt_tokens": 64,
                "generated_tokens": 134, "execution_time_s": 0.6,
                "decode_s": 0.45,
            })
    recompute_energy(exp, reanalyze=False)
    rows = {r["location"]: r for r in RunTableStore(exp).read()}
    assert rows["remote"]["remote_modeled_decode_s"] is not None
    assert rows["on_device"]["remote_modeled_decode_s"] is None


def test_recompute_does_not_bake_default_chips(tmp_path):
    """Without an explicit --chips map the fallback topology is USED but
    not persisted — a later recompute with the correct map must still
    take effect (code-review round-4 finding)."""
    import csv

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        recompute_energy,
    )

    exp = tmp_path / "nochips"
    exp.mkdir()
    cols = [
        "__run_id", "__done", "model", "location", "length",
        "prompt_tokens", "generated_tokens", "execution_time_s", "decode_s",
    ]
    with (exp / "run_table.csv").open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerow({
            "__run_id": "run_0_repetition_0", "__done": "DONE",
            "model": "qwen2:1.5b", "location": "remote", "length": 100,
            "prompt_tokens": 64, "generated_tokens": 134,
            "execution_time_s": 0.6, "decode_s": 0.45,
        })
    recompute_energy(exp, reanalyze=False)  # default topology: remote=8
    (row,) = RunTableStore(exp).read()
    e_default = row["energy_model_J"]
    assert row["chips"] is None  # fallback not baked in
    # the corrected topology still takes effect on a second pass...
    recompute_energy(
        exp, reanalyze=False, n_chips_by_location={"remote": 4}
    )
    (row,) = RunTableStore(exp).read()
    assert row["energy_model_J"] != e_default
    # ...and an operator-asserted map IS persisted
    assert row["chips"] == 4


def test_full_study_on_fake_counter_channel_prefers_measured(
    tmp_path, monkeypatch
):
    """VERDICT round-5 directive #6 e2e: with a live power counter (fake
    source injected at the module seam the profiler's default chain
    reads), the full study records tpu_energy_J per run AND the study's
    own post-hoc analysis selects the MEASURED channel as the energy
    metric — H2 runs unrestricted (no definitional exclusions). This is
    the path a real counter-bearing TPU VM takes with zero config
    changes; it caught after_experiment's fixed metric list silently
    excluding measured channels."""
    import json

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers import tpu

    monkeypatch.setattr(tpu, "_try_read_power_w", lambda: 120.0)
    # a slower fake: each run's window must span several 0.1 s counter
    # sampling periods or the trapezoid integration has nothing to sum
    slow_fake = FakeBackend(tokens_per_s=400.0, simulate_delay=True)
    config = LlmEnergyConfig(
        models=["qwen2:1.5b", "gemma:2b"],
        locations=["on_device", "remote"],
        lengths=[100],
        repetitions=2,
        results_output_path=tmp_path,
        cooldown_ms=0,
        backends={"on_device": slow_fake, "remote": slow_fake},
        shuffle=True,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
        TpuPowerCounterProfiler,
    )

    assert any(
        isinstance(p, TpuPowerCounterProfiler) for p in config.profilers
    ), "a live counter source must wire the profiler into the study"
    ExperimentController(config, echo=False).do_experiment()
    exp = tmp_path / "llm_energy_tpu"
    rows = RunTableStore(exp).read()
    assert rows and all(r["__done"] == RunProgress.DONE for r in rows)
    for r in rows:
        assert r["tpu_energy_J"] is not None and r["tpu_energy_J"] > 0
        assert r["tpu_avg_power_W"] == pytest.approx(120.0, rel=0.05)
    report = json.loads((exp / "analysis_report.json").read_text())
    assert "tpu_energy_J" in report["metrics"]
    # measured channel outranks the model as THE energy metric
    assert report["variance_check"]["metric"] == "tpu_energy_J"
    assert report.get("h2_energy_is_modelled") is False
    # unrestricted H2: nothing annotated definitional
    for per_metric in report["h2_spearman"].values():
        assert not any(h.get("definitional") for h in per_metric.values())
