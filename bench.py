"""Benchmark: decode throughput of the JAX engine on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline derivation (BASELINE.md): the reference's on-device treatment
generates 1000 words in 43.35 s mean wall-time (IQR-filtered, all models) —
1000 · 4/3 ≈ 1333 tokens → **30.8 tokens/s** on the M2 via Ollama. This bench
greedy-decodes the same flagship-class model (qwen2:1.5b, full architecture)
on one TPU chip and reports steady-state decode tokens/s; ``vs_baseline``
> 1 means faster than the reference's on-device rate.

Weights are int8 weight-only quantized on the accelerator (activations and
KV stay bf16): decode is HBM-bandwidth-bound, and the reference's own
baseline models are Ollama defaults — 4-bit GGUF quants — so quantized
serving is the matching configuration, not an extra trick. The "quantize"
field in the JSON records it.

Without a TPU backend the default entry measures nothing and exits
non-zero (a CPU number under this metric name would be read as a device
number); the named sub-benches pin parity and counts on CPU. The line names
the device it ran on.
"""

import dataclasses
import json
import sys
import time

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.device import (
    device_report,
    on_tpu,
)

BASELINE_TOKENS_PER_S = 1000.0 * (4.0 / 3.0) / 43.35  # ≈ 30.75 (BASELINE.md)
# Batch timing discipline — used by BOTH the measurement loop and the
# emitted JSON so the self-describing metadata cannot drift from what ran.
BATCH_TIMED_RUNS = 2
BATCH_STAT = "best"  # max over the timed windows


def _attach_obs(line: dict) -> None:
    """Attach the obs registry snapshot (`obs_metrics`), the flight-
    recorder summary (`obs_flight`: event counts by type + drop count)
    and — when any SLO engine is live — the per-objective attainment/
    burn state (`obs_slo`) to a bench JSON line, so a BENCH_*.json row
    records not just the figures but the scheduler/engine decisions
    (slices, joins, retirements, fallbacks) and contract state behind
    them. Guarded: the perf line must never die on telemetry."""
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.flight import (
            FLIGHT,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
            REGISTRY,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.slo import (
            active_snapshot,
        )

        snap = REGISTRY.snapshot()
        if snap:
            line["obs_metrics"] = snap
        flight = FLIGHT.summary()
        if flight.get("events_total"):
            line["obs_flight"] = flight
        slo = active_snapshot()
        if slo:
            line["obs_slo"] = slo
    except Exception:
        pass


def continuous_batching_bench() -> int:
    """A/B of the two request schedulers under STAGGERED (Poisson)
    arrivals: window dispatch (batches run to completion) vs the
    iteration-level continuous scheduler (admit/step/retire at decode-
    step granularity — serve/scheduler.py, engine/stepped.py).

    CPU-functional and fake-clock-free: a depth-reduced real JaxEngine
    decodes real tokens on whatever backend JAX has, and the arrival
    process sleeps real wall-clock (seeded exponential inter-arrival via
    scripts/poisson_load.py). The figures that matter are the RELATIVE
    ones — p50/p95 TTFT, completion latency, aggregate tokens/s at the
    same arrival trace — recorded in docs/PERF.md "Continuous vs window
    batching". Prints ONE JSON line.
    """
    import dataclasses as _dc
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        BatchScheduler,
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        # CPU-functional: the tiny architecture decodes real tokens in
        # ~ms steps, so the latency SHAPES under staggered load are
        # real while the full-width model's per-shape XLA compiles
        # (minutes each on CPU) stay out of the bench
        cfg = cfg.tiny()
    engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16 if on_accelerator else jnp.float32,
        decode_attention="auto" if on_accelerator else None,
    )

    n = int(_os.environ.get("BENCH_CB_REQUESTS", "18"))
    mean_ms = float(_os.environ.get("BENCH_CB_INTERARRIVAL_MS", "60"))
    budgets = (8, 16, 96)  # mixed targets: arrivals straddle the long rows
    # one prompt bucket (all < 32 tokens): the A/B measures scheduling,
    # not prefill-shape compile churn
    prompts = ("alpha beta", "gamma delta epsilon", "zeta eta")
    workload = build_workload(
        n, mean_ms / 1e3, seed=7, model=cfg.name, budgets=budgets,
        prompts=prompts,
        stop_at_eos=False,  # fixed lengths: both schedulers do equal work
    )

    # Warm every compiled shape OUTSIDE the measured traces (both
    # schedulers replay the same arrival trace; neither may pay XLA).
    warm = [req for _, req in workload[:6]]
    engine.generate_batch(warm)
    for req in {r.max_new_tokens: r for r in warm}.values():
        engine.generate(req)
    sess = engine.decode_open(warm, reserve_rows=2 * len(warm))
    while sess.active:
        sess.step()
    sess.close()

    results = {}
    for mode, make in (
        ("window", lambda: BatchScheduler(engine, window_s=0.05)),
        ("continuous", lambda: ContinuousScheduler(engine)),
    ):
        sched = make()
        sched.start()
        try:
            records = run_load(sched.submit, workload)
        finally:
            sched.stop()
        results[mode] = summarize(records)

    line = {
        "metric": "continuous_batching",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "n_layers": cfg.n_layers,
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "budgets": list(budgets),
        "window": results["window"],
        "continuous": results["continuous"],
        "ttft_p50_speedup": (
            round(
                results["window"]["ttft_p50_s"]
                / results["continuous"]["ttft_p50_s"],
                2,
            )
            if results["continuous"].get("ttft_p50_s")
            else None
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def chunked_join_bench() -> int:
    """A/B of the continuous scheduler's JOIN policy under a
    heavy-tailed (lognormal) prompt-length Poisson trace: synchronous
    one-shot joins (PR 3 — the whole prompt prefills between two decode
    slices) vs chunked joins (PR 4 — token-budgeted prefill chunks
    interleaved with slices, `--prefill-chunk-tokens`).

    Headline figures: the IN-FLIGHT inter-token gap p99 (the wall
    between two consecutive decode-slice completions that live rows sat
    through — what a caller mid-decode experiences when a long-prompt
    joiner streams in; with sync joins one gap swallows the joiner's
    whole prefill, with chunked joins every gap is bounded by one slice
    + one chunk) and joiner TTFT p95, at the same seeded arrival trace,
    plus aggregate tok/s (chunking must not cost throughput) and
    bit-parity of every stream vs solo generate(). CPU-functional like
    the continuous_batching bench: tiny real architecture, real tokens,
    real wall-clock; RELATIVE positions are the result (docs/PERF.md
    "Chunked join-prefill"). Prints ONE JSON line.
    """
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, percentile, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        # room for the heavy tail: prompts to ~352 tokens + budgets
        cfg = cfg.tiny(max_seq_len=1024)
    engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16 if on_accelerator else jnp.float32,
        decode_attention="auto" if on_accelerator else None,
    )

    n = int(_os.environ.get("BENCH_CJ_REQUESTS", "14"))
    mean_ms = float(_os.environ.get("BENCH_CJ_INTERARRIVAL_MS", "50"))
    chunk_tokens = int(_os.environ.get("BENCH_CJ_CHUNK_TOKENS", "64"))
    slice_steps = int(_os.environ.get("BENCH_CJ_SLICE_STEPS", "8"))
    # request 0 (the session anchor) rotates onto the LONG budget so the
    # session outlives the arrivals (160 steps of slices spans the whole
    # trace — heavy-tailed joiners must land MID-FLIGHT, the case under
    # test); anchor_longest gives it the longest prompt so the session
    # cache fits every later joiner — the A/B then varies ONLY the join
    # policy, not capacity feasibility
    budgets = (160, 12, 24)
    workload = build_workload(
        n,
        mean_ms / 1e3,
        seed=11,
        model=cfg.name,
        budgets=budgets,
        stop_at_eos=False,  # fixed lengths: both arms do equal work
        prompt_len_dist="lognormal",
        prompt_len_median=40.0,
        prompt_len_sigma=1.1,
        prompt_len_max=352,
        anchor_longest=True,
    )
    prompt_tokens = [len(req.prompt) + 1 for _, req in workload]

    # solo references: parity oracle AND warm-up of the solo shapes
    solo = {id(req): engine.generate(req).tokens for _, req in workload}

    def run_mode(chunked: bool):
        sched = ContinuousScheduler(
            engine,
            slice_steps=slice_steps,
            prefill_chunk_tokens=chunk_tokens,
            chunked_joins=chunked,
        )
        gaps = []
        sched.slice_gap_sink = lambda gap_s, rows: gaps.append(gap_s)
        tokens_by_req = {}

        def submit(req):
            res = sched.submit(req)
            tokens_by_req[id(req)] = res.tokens
            return res

        sched.start()
        try:
            records = run_load(submit, workload)
        finally:
            sched.stop()
        joiners = [r for r in records if r.get("joined")]
        joiner_ttfts = [
            r["ttft_s"] for r in joiners if r.get("ttft_s") is not None
        ]
        return {
            **summarize(records),
            "inflight_gap_p99_s": (
                round(percentile(gaps, 99), 4) if gaps else None
            ),
            "inflight_gap_max_s": round(max(gaps), 4) if gaps else None,
            "slice_gaps_observed": len(gaps),
            "joined": len(joiners),
            "join_chunks_total": sum(
                r.get("join_chunks") or 0 for r in records
            ),
            "joiner_ttft_p95_s": (
                round(percentile(joiner_ttfts, 95), 4)
                if joiner_ttfts
                else None
            ),
            "parity_vs_solo": all(
                tokens_by_req.get(i) == toks for i, toks in solo.items()
            ),
        }

    # warm BOTH arms outside the measured traces (session shapes, chunk
    # prefill buckets, stepped decode fns — neither arm may pay XLA)
    run_mode(False)
    run_mode(True)
    results = {"sync": run_mode(False), "chunked": run_mode(True)}

    line = {
        "metric": "chunked_join",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "n_layers": cfg.n_layers,
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "budgets": list(budgets),
        "prompt_len": {
            "dist": "lognormal", "median": 40.0, "sigma": 1.1,
            "max": 352, "anchor_longest": True,
            "drawn_min": min(prompt_tokens),
            "drawn_max": max(prompt_tokens),
        },
        "prefill_chunk_tokens": chunk_tokens,
        "decode_slice_steps": slice_steps,
        **results,
        "inflight_gap_p99_ratio": (
            round(
                results["sync"]["inflight_gap_p99_s"]
                / results["chunked"]["inflight_gap_p99_s"],
                2,
            )
            if results["sync"]["inflight_gap_p99_s"]
            and results["chunked"]["inflight_gap_p99_s"]
            else None
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def streaming_cancellation_bench() -> int:
    """A/B of streaming delivery + mid-stream cancellation (ISSUE 6)
    under the same seeded Poisson trace, three arms on one tiny PAGED
    JaxEngine through the continuous scheduler:

    - **buffered**: blocking submits — the pre-streaming baseline; a
      25%-cancellation INTENT is recorded but cannot take effect, so
      every abandoned row decodes to its full budget;
    - **streaming**: every request consumes its per-slice egress
      channel, nobody cancels — the tok/s-regression guard (streamed
      delivery must not cost aggregate throughput on the uncancelled
      subset);
    - **streaming_cancel**: the same trace with the 25% of clients
      actually hanging up after their drawn token count — rows retire
      mid-flight (reason="cancelled") and their pages recycle.

    Headline figures: TTFT-at-first-chunk percentiles, the paged pool's
    HIGH-WATER page occupancy (cancellation keeps it lower), and the
    GOODPUT RATIO — tokens a client actually wanted, over row-steps the
    device executed (llm_engine_stepped_tokens_total deltas). Cancelled
    rows stop consuming steps, so the ratio must improve vs the
    buffered arm, which keeps decoding for nobody. CPU-functional,
    seeded, relative positions are the result (docs/PERF.md "Streaming
    delivery + cancellation"). Prints ONE JSON line.
    """
    import os as _os
    import sys as _sys
    import threading as _threading
    import time as _time

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import (
        build_cancellations,
        build_workload,
        channel_chunks,
        percentile,
        run_load,
        summarize,
    )

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
        STEPPED_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        cfg = cfg.tiny()
    engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16 if on_accelerator else jnp.float32,
        decode_attention="auto" if on_accelerator else None,
        paged_kv=True,  # the pool high-water figure is a paged-pool story
    )

    n = int(_os.environ.get("BENCH_SC_REQUESTS", "16"))
    mean_ms = float(_os.environ.get("BENCH_SC_INTERARRIVAL_MS", "50"))
    slice_steps = int(_os.environ.get("BENCH_SC_SLICE_STEPS", "8"))
    # every 4th request draws the LONG budget — and that quarter is the
    # cancellation target: the realistic abandonment case (a client who
    # has read enough of a long generation hangs up) and the only one
    # where reclaiming matters — a cancelled short row's session is
    # still bounded by its longest companion, so cancelling short rows
    # saves no bucket-steps by construction
    budgets = (128, 12, 24, 48)
    prompts = ("alpha beta", "gamma delta epsilon", "zeta eta")
    workload = build_workload(
        n, mean_ms / 1e3, seed=13, model=cfg.name, budgets=budgets,
        prompts=prompts, stop_at_eos=False,
    )
    # seeded per-request hang-up points, applied to the long-budget
    # quarter: entry i = tokens delivered before client i disconnects
    # (None = runs to completion). Same plan in every arm.
    draws = build_cancellations(n, 1.0, after_tokens=(4, 24), seed=13)
    cancellations = [
        d if req.max_new_tokens == max(budgets) else None
        for d, (_, req) in zip(draws, workload)
    ]
    cancel_frac = sum(1 for c in cancellations if c is not None) / n
    # tokens each client actually WANTS under the cancellation intent —
    # the goodput numerator for every arm (a buffered arm still decodes
    # the full budget; the excess is the waste streaming reclaims)
    useful = [
        min(c, req.max_new_tokens) if c is not None else req.max_new_tokens
        for c, (_, req) in zip(cancellations, workload)
    ]

    # solo warm-up: every compiled shape + the parity oracle
    solo = {id(req): engine.generate(req).tokens for _, req in workload}
    warm_sess = engine.decode_open(
        [req for _, req in workload[:4]], reserve_rows=8
    )
    while warm_sess.active:
        warm_sess.step(slice_steps)
    warm_sess.close()

    # run_load streams exactly the requests with a cancel-after plan, so
    # the all-streaming arms give no-cancel requests an unreachable
    # cancel point (every token streams, the stream runs to completion)
    NEVER = 1 << 30
    stream_all_plan = [c if c is not None else NEVER for c in cancellations]

    def run_arm(cancel_plan):
        sched = ContinuousScheduler(engine, slice_steps=slice_steps)
        # paged-pool high-water sampler: peak pages in use across the
        # arm (the scheduler's live debug handle; /debug/state's twin)
        high_water = [0]
        stop_probe = _threading.Event()

        def probe():
            while not stop_probe.is_set():
                dbg = sched._dbg
                if dbg is not None:
                    try:
                        pool = dbg[0].pool
                        in_use = pool.n_pages - pool.free_pages
                        high_water[0] = max(high_water[0], in_use)
                    except Exception:  # noqa: BLE001 — racing close()
                        pass
                _time.sleep(0.004)

        tokens_by_req = {}

        def submit(req):
            res = sched.submit(req)
            tokens_by_req[id(req)] = res.tokens
            return res

        def stream_submit(req):
            def recording():
                inner = channel_chunks(sched.submit_stream(req))
                try:
                    for chunk in inner:
                        if chunk.done and chunk.result is not None:
                            tokens_by_req[id(req)] = chunk.result.tokens
                        yield chunk
                finally:
                    inner.close()  # early close propagates the cancel

            return recording()

        stepped0 = STEPPED_C.labels().value
        sched.start()
        prober = _threading.Thread(target=probe, daemon=True)
        prober.start()
        try:
            records = run_load(
                submit,
                workload,
                stream_submit=(
                    stream_submit if cancel_plan is not None else None
                ),
                cancellations=cancel_plan,
            )
        finally:
            stop_probe.set()
            sched.stop()
            prober.join(timeout=2)
        stepped = STEPPED_C.labels().value - stepped0
        ttfts = [r["ttft_s"] for r in records if r.get("ttft_s") is not None]
        uncancelled = [
            r for r in records
            if "error" not in r and not r.get("cancelled")
        ]
        return {
            **summarize(records),
            "ttft_first_chunk_p50_s": (
                round(percentile(ttfts, 50), 4) if ttfts else None
            ),
            "ttft_first_chunk_p95_s": (
                round(percentile(ttfts, 95), 4) if ttfts else None
            ),
            "pool_high_water_pages": high_water[0],
            "stepped_row_steps": int(stepped),
            "goodput_ratio": (
                round(sum(useful) / stepped, 3) if stepped else None
            ),
            "uncancelled_tokens": sum(r["tokens"] for r in uncancelled),
            "parity_vs_solo": all(
                tokens_by_req.get(i) == toks
                for i, toks in solo.items()
                if i in tokens_by_req
            ),
        }

    # warm the arm machinery itself (join shapes, stream plumbing)
    run_arm([NEVER] * n)
    results = {
        "buffered": run_arm(None),
        "streaming": run_arm([NEVER] * n),
        "streaming_cancel": run_arm(stream_all_plan),
    }

    line = {
        "metric": "streaming_cancellation",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "n_layers": cfg.n_layers,
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "budgets": list(budgets),
        "cancel_frac": cancel_frac,
        "planned_cancellations": sum(
            1 for c in cancellations if c is not None
        ),
        "decode_slice_steps": slice_steps,
        **results,
        "streaming_vs_buffered_tok_s": (
            round(
                results["streaming"]["agg_tokens_per_s"]
                / results["buffered"]["agg_tokens_per_s"],
                3,
            )
            if results["buffered"]["agg_tokens_per_s"]
            else None
        ),
        "goodput_ratio_gain": (
            round(
                results["streaming_cancel"]["goodput_ratio"]
                / results["buffered"]["goodput_ratio"],
                2,
            )
            if results["buffered"]["goodput_ratio"]
            else None
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def tenant_attribution_bench() -> int:
    """Per-tenant slice-attribution accuracy (ISSUE 20): one seeded
    Poisson trace, two tenants at a 70/30 mix, driven through the
    continuous scheduler so rows JOIN a shared decode session
    mid-flight, with a seeded fraction of clients hanging up
    mid-stream. Two arms over the SAME requests:

    - **shared**: the full trace at speed — joiners, cancellations,
      token-share slice splits; each completed request's Joules come
      from its ``extras["energy_model"]`` close-out;
    - **solo** (ground truth): the shared arm's COMPLETED requests
      replayed one at a time through a fresh scheduler — every row
      alone in its session, so its attribution is trivially exact.

    The engine is the fake backend with a per-token synthetic energy
    price: its model charges decode tokens and nothing else, so the
    shared arm's per-tenant J/token must reproduce the solo figure
    EXACTLY — unlike a real batch (where amortizing the weight stream
    across rows is the point), any deviation here is tokens billed to
    the wrong row, not physics. The headline is the worst per-tenant
    attribution error (target <5%; the conservation tests pin the same
    split at 1e-6 granularity), cross-checked against the server-side
    tenant table the scheduler accounted into. Prints ONE JSON line.
    """
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    from poisson_load import (
        build_cancellations,
        build_workload,
        channel_chunks,
        run_load,
        summarize,
    )

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import (
        tenants as obs_tenants,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )

    JPT = 0.21  # synthetic Joules per decode token
    n = int(_os.environ.get("BENCH_TA_REQUESTS", "24"))
    mean_ms = float(_os.environ.get("BENCH_TA_INTERARRIVAL_MS", "15"))
    backend = FakeBackend(
        tokens_per_s=600.0, simulate_delay=True, joules_per_token=JPT
    )
    workload = build_workload(
        n, mean_ms / 1e3, seed=20, model="bench:1b",
        budgets=(64, 12, 24, 48), stop_at_eos=False,
        tenant_mix={"a": 0.7, "b": 0.3},
    )
    cancellations = build_cancellations(n, 0.25, after_tokens=(4, 16), seed=20)

    obs_tenants.reset_tenants()
    sched = ContinuousScheduler(backend)
    sched.start()
    try:
        shared_records = run_load(
            sched.submit,
            workload,
            stream_submit=lambda req: channel_chunks(
                sched.submit_stream(req)
            ),
            cancellations=cancellations,
        )
    finally:
        sched.stop()
    table = obs_tenants.snapshot()["tenants"]

    # ground truth: the completed requests, one at a time — nothing to
    # share a slice with, so per-request attribution is exact by
    # construction (and the fake is deterministic, so tokens replay)
    done = [
        (i, rec) for i, rec in enumerate(shared_records)
        if "error" not in rec and not rec.get("cancelled")
    ]
    solo_sched = ContinuousScheduler(backend)
    solo_sched.start()
    try:
        solo_J = {}
        for i, _rec in done:
            res = solo_sched.submit(workload[i][1])
            solo_J[i] = (res.extras or {})["energy_model"]["J"]
    finally:
        solo_sched.stop()

    def per_tenant(figures):
        out = {}
        for i, rec in done:
            t = rec["tenant"]
            acct = out.setdefault(t, {"joules": 0.0, "tokens": 0})
            acct["joules"] += figures(i, rec)
            acct["tokens"] += rec["tokens"]
        return {
            t: round(a["joules"] / a["tokens"], 6)
            for t, a in out.items() if a["tokens"]
        }

    shared_jpt = per_tenant(lambda i, rec: rec["joules"])
    solo_jpt = per_tenant(lambda i, rec: solo_J[i])
    errors = {
        t: round(abs(shared_jpt[t] - solo_jpt[t]) / solo_jpt[t], 6)
        for t in solo_jpt
    }
    max_error = max(errors.values()) if errors else None

    # cross-check: the scheduler accounted the SAME joules into the
    # tenant table the /debug/tenants surface serves. A client that
    # hangs up in the same instant its row finishes records "cancelled"
    # while the server legitimately closes the row out "ok" (with its
    # Joules) — so the table may exceed the client-side sum by at most
    # those rows' full budgets, and never fall below it.
    def _tenant_ok(check):
        for t in shared_jpt:
            client_J = sum(
                rec["joules"] for _i, rec in done if rec["tenant"] == t
            )
            slack = JPT * sum(
                workload[i][1].max_new_tokens
                for i, rec in enumerate(shared_records)
                if rec.get("tenant") == t and rec.get("cancelled")
            )
            if not check(table.get(t, {}).get("joules", 0.0), client_J, slack):
                return False
        return True

    table_agrees = _tenant_ok(
        lambda table_J, client_J, slack: -1e-6
        <= table_J - client_J
        <= slack + 1e-6
    )

    summary = summarize(shared_records)
    line = {
        "metric": "tenant_attribution",
        "unit": "relative_error",
        "value": max_error,
        "target": 0.05,
        "passed": max_error is not None and max_error < 0.05,
        "model": "bench:1b",
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "tenant_mix": {"a": 0.7, "b": 0.3},
        "joules_per_token_model": JPT,
        "completed": len(done),
        "cancelled": summary["cancelled"],
        "rows_joined": sum(
            1 for _i, r in done if r.get("joined")
        ),
        "shared_j_per_token": shared_jpt,
        "solo_j_per_token": solo_jpt,
        "attribution_error": errors,
        "tenant_table_agrees": table_agrees,
        "tenants": summary.get("tenants"),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0 if line["passed"] and table_agrees else 1


def preemption_overload_bench() -> int:
    """SLO tiers + mid-flight preemption under overload (ISSUE 11):
    the SAME seeded tiered Poisson trace — a 2×-pool-saturating storm
    of long LOW-tier rows with a short HIGH-tier minority riding a
    per-request deadline — replayed through three continuous-scheduler
    arms on one tiny PAGED JaxEngine:

    - **shed_only** (``preempt_policy="off"``): the pre-ISSUE-11
      overload response — a high-tier ticket that cannot be admitted
      waits behind low-tier long rows until its deadline sheds it;
    - **preempt_swap**: the victim's KV pages spill to host memory and
      restore bit-exactly at resume;
    - **preempt_recompute**: the victim's KV is dropped and
      re-prefilled through the chunked-join machinery at resume.

    Headlines: HIGH-TIER TTFT p99 + served fraction (the SLO the tiers
    exist for), total GOODPUT tokens (llm_engine_goodput_tokens_total
    delta — preemption must not torch aggregate useful work), swap
    bytes out/in, and PARITY of every resumed row against its solo
    generate() oracle. CPU-functional, seeded; relative positions are
    the result (docs/PERF.md "SLO tiers + preemption"). One JSON line.
    """
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.detect import (
        GOODPUT_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        SWAP_BYTES_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.protocol import (
        PRIORITY_TIERS,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        cfg = cfg.tiny()
    engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16 if on_accelerator else jnp.float32,
        decode_attention="auto" if on_accelerator else None,
        paged_kv=True,  # page swap is the tentpole under test
    )

    n = int(_os.environ.get("BENCH_PO_REQUESTS", "18"))
    mean_ms = float(_os.environ.get("BENCH_PO_INTERARRIVAL_MS", "15"))
    deadline_ms = float(_os.environ.get("BENCH_PO_DEADLINE_MS", "2500"))
    slice_steps = int(_os.environ.get("BENCH_PO_SLICE_STEPS", "8"))
    high = PRIORITY_TIERS["high"]
    # long low-tier budgets vs a storm-tight arrival clock: concurrent
    # page demand runs ~2× the pool the first arrival sizes (the bench
    # reports the measured ratio as overload_x)
    budgets = (96, 128, 48)
    workload = build_workload(
        n, mean_ms / 1e3, seed=29, model=cfg.name, budgets=budgets,
        stop_at_eos=False, deadline_ms=deadline_ms,
        tier_mix={"high": 0.25, "low": 0.75},
    )
    solo = {id(req): engine.generate(req).tokens for _, req in workload}

    # warm every compiled shape once so arm walls compare policies, not
    # compilation
    warm = engine.decode_open(
        [req for _, req in workload[:4]], reserve_rows=8
    )
    while warm.active:
        warm.step(slice_steps)
    warm.close()

    def run_arm(policy):
        sched = ContinuousScheduler(
            engine,
            slice_steps=slice_steps,
            preempt_policy=policy,
            preempt_max_wait_s=5.0,
        )
        tokens_by_req = {}
        extras_by_req = {}
        pool_stats = {"pages": 0, "high_water": 0}

        def submit(req):
            res = sched.submit(req)
            tokens_by_req[id(req)] = res.tokens
            extras_by_req[id(req)] = (res.extras or {}).get("sched", {})
            dbg = sched._dbg
            if dbg is not None:
                try:
                    pool = dbg[0].pool
                    pool_stats["pages"] = pool.n_pages
                    pool_stats["high_water"] = max(
                        pool_stats["high_water"],
                        pool.n_pages - pool.free_pages,
                    )
                except Exception:  # noqa: BLE001 — racing close()
                    pass
            return res

        goodput0 = GOODPUT_C.labels().value
        swap_out0 = SWAP_BYTES_C.labels(direction="out").value
        swap_in0 = SWAP_BYTES_C.labels(direction="in").value
        sched.start()
        try:
            records = run_load(submit, workload)
        finally:
            sched.stop()
        resumed_ids = [
            i for i, ex in extras_by_req.items() if ex.get("resumed")
        ]
        # page demand the trace actually put up, relative to the pool
        demand_pages = None
        if pool_stats["pages"]:
            per_row = [
                -(-(len(req.prompt) + 1 + req.max_new_tokens) // 128)
                for _, req in workload
            ]
            demand_pages = sum(per_row)
        return {
            **summarize(records),
            "goodput_tokens": int(GOODPUT_C.labels().value - goodput0),
            "swap_bytes_out": int(
                SWAP_BYTES_C.labels(direction="out").value - swap_out0
            ),
            "swap_bytes_in": int(
                SWAP_BYTES_C.labels(direction="in").value - swap_in0
            ),
            "resumed_rows": len(resumed_ids),
            "resumed_parity_vs_solo": all(
                tokens_by_req.get(i) == solo[i] for i in resumed_ids
            ),
            "pool_pages": pool_stats["pages"],
            "pool_high_water_pages": pool_stats["high_water"],
            "overload_x": (
                round(demand_pages / pool_stats["pages"], 2)
                if pool_stats["pages"]
                else None
            ),
        }

    results = {
        "shed_only": run_arm("off"),
        "preempt_swap": run_arm("swap"),
        "preempt_recompute": run_arm("recompute"),
    }

    def high_p99(arm):
        return (results[arm].get("tiers", {}).get(str(high), {})).get(
            "ttft_p99_s"
        )

    base_p99 = high_p99("shed_only")
    line = {
        "metric": "preemption_overload",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "n_layers": cfg.n_layers,
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "deadline_ms": deadline_ms,
        "budgets": list(budgets),
        "tier_mix": {"high": 0.25, "low": 0.75},
        "decode_slice_steps": slice_steps,
        **results,
        "high_tier_ttft_p99_gain_swap": (
            round(base_p99 / high_p99("preempt_swap"), 2)
            if base_p99 and high_p99("preempt_swap")
            else None
        ),
        "high_tier_ttft_p99_gain_recompute": (
            round(base_p99 / high_p99("preempt_recompute"), 2)
            if base_p99 and high_p99("preempt_recompute")
            else None
        ),
        "goodput_ratio_swap": (
            round(
                results["preempt_swap"]["goodput_tokens"]
                / results["shed_only"]["goodput_tokens"],
                3,
            )
            if results["shed_only"]["goodput_tokens"]
            else None
        ),
        "goodput_ratio_recompute": (
            round(
                results["preempt_recompute"]["goodput_tokens"]
                / results["shed_only"]["goodput_tokens"],
                3,
            )
            if results["shed_only"]["goodput_tokens"]
            else None
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def shared_prefix_bench() -> int:
    """A/B of shared-prefix copy-on-write paging (ISSUE 7) on a
    high-share Poisson trace: the chunked-join baseline (every joiner
    prefills its whole prompt) vs `prefix_share=True` (joiners map the
    anchor's refcounted read-only prefix pages and chunk-prefill only
    the divergent tail).

    Headline figures at the same seeded trace: joiner TTFT p50/p95,
    prefill tokens actually COMPUTED (prompt tokens minus
    llm_prefix_hit_tokens_total's delta), pool high-water (peak pages
    in use — shared pages billed once shrink it), aggregate tok/s
    (sharing must not cost throughput), and bit-parity of every stream
    vs solo generate() in BOTH arms. A second part drives sessions
    directly on the bf16 AND int8 paged pools: N sharers admitted then
    all retired (incl. a mid-flight cancellation) must restore the
    pool free-count EXACTLY, and close() must restore it fully.
    CPU-functional like the chunked_join bench; RELATIVE positions are
    the result (docs/PERF.md "Shared-prefix CoW paging"). Prints ONE
    JSON line.
    """
    import os as _os
    import sys as _sys
    import threading as _threading

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, percentile, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import (
        _POOL_FREE,
        _POOL_PAGES,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.prefix import (
        PREFIX_COW_COPIES_C,
        PREFIX_HIT_TOKENS_C,
        PREFIX_SHARED_PAGES_G,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        # room for the 192-token shared prefix + tails + budgets
        cfg = cfg.tiny(max_seq_len=1024)
    dtype = jnp.bfloat16 if on_accelerator else jnp.float32

    # arrivals dense enough that admission prefill CONTENDS with decode
    # (the regime sharing exists for: under a sparse trace both arms
    # idle between joiners and the A/B only moves TTFT)
    n = int(_os.environ.get("BENCH_SP_REQUESTS", "16"))
    mean_ms = float(_os.environ.get("BENCH_SP_INTERARRIVAL_MS", "25"))
    chunk_tokens = int(_os.environ.get("BENCH_SP_CHUNK_TOKENS", "64"))
    slice_steps = int(_os.environ.get("BENCH_SP_SLICE_STEPS", "8"))
    share_frac = float(_os.environ.get("BENCH_SP_SHARE_FRAC", "0.75"))
    prefix_tokens = int(_os.environ.get("BENCH_SP_PREFIX_TOKENS", "192"))
    # anchor rotates onto the LONG budget so the session outlives the
    # arrivals and carries the page-backed shared prefix (see
    # anchor_shared_prefix in scripts/poisson_load.py)
    budgets = (192, 10, 16)
    workload = build_workload(
        n,
        mean_ms / 1e3,
        seed=7,
        model=cfg.name,
        budgets=budgets,
        stop_at_eos=False,  # fixed lengths: both arms do equal work
        shared_prefix_frac=share_frac,
        prefix_pool=1,
        shared_prefix_tokens=prefix_tokens,
        anchor_shared_prefix=True,
    )
    prompt_tokens = [len(req.prompt) + 1 for _, req in workload]
    shared_requests = sum(
        1 for _, req in workload if req.prompt.startswith("<sys0>")
    )

    def make_engine(share: bool) -> JaxEngine:
        return JaxEngine(
            registry={cfg.name: cfg},
            dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            paged_kv=True,
            prefix_share=share,
        )

    engines = {False: make_engine(False), True: make_engine(True)}
    # solo references: parity oracle AND warm-up of the solo shapes
    solo = {
        id(req): engines[False].generate(req).tokens for _, req in workload
    }

    def run_arm(share: bool):
        engine = engines[share]
        if share and engine.prefix_store is not None:
            # the ISSUE-14 store is ENGINE-lifetime: drop the previous
            # run's publications so every arm (warm and measured)
            # starts empty — this bench measures the WITHIN-session
            # win at PR-7 semantics; bench.py radix_prefix measures
            # the cross-session story deliberately
            engine.prefix_store.release_all()
        sched = ContinuousScheduler(
            engine,
            slice_steps=slice_steps,
            prefill_chunk_tokens=chunk_tokens,
            chunked_joins=True,
        )
        hits0 = PREFIX_HIT_TOKENS_C.labels().value
        cow0 = PREFIX_COW_COPIES_C.labels().value
        tokens_by_req = {}
        high_water = {"pages": 0.0, "shared": 0.0}
        stop_probe = _threading.Event()

        def probe():
            while not stop_probe.wait(0.01):
                total = _POOL_PAGES.labels().value
                free = _POOL_FREE.labels().value
                high_water["pages"] = max(
                    high_water["pages"], total - free
                )
                high_water["shared"] = max(
                    high_water["shared"], PREFIX_SHARED_PAGES_G.labels().value
                )

        def submit(req):
            res = sched.submit(req)
            tokens_by_req[id(req)] = res.tokens
            return res

        sched.start()
        prober = _threading.Thread(target=probe, daemon=True)
        prober.start()
        try:
            records = run_load(submit, workload)
        finally:
            sched.stop()
            stop_probe.set()
            prober.join(timeout=2)
        joiners = [r for r in records if r.get("joined")]
        joiner_ttfts = [
            r["ttft_s"] for r in joiners if r.get("ttft_s") is not None
        ]
        hit_tokens = PREFIX_HIT_TOKENS_C.labels().value - hits0
        return {
            **summarize(records),
            "joined": len(joiners),
            "joiner_ttft_p50_s": (
                round(percentile(joiner_ttfts, 50), 4)
                if joiner_ttfts
                else None
            ),
            "joiner_ttft_p95_s": (
                round(percentile(joiner_ttfts, 95), 4)
                if joiner_ttfts
                else None
            ),
            "prefill_tokens_total": sum(prompt_tokens),
            "prefix_hit_tokens": int(hit_tokens),
            "prefill_tokens_computed": int(sum(prompt_tokens) - hit_tokens),
            "cow_copies": int(PREFIX_COW_COPIES_C.labels().value - cow0),
            "pool_high_water_pages": int(high_water["pages"]),
            "shared_pages_high_water": int(high_water["shared"]),
            "parity_vs_solo": all(
                tokens_by_req.get(i) == toks for i, toks in solo.items()
            ),
        }

    # warm BOTH arms outside the measured traces (session shapes, chunk
    # prefill buckets, stepped decode fns — neither arm may pay XLA)
    run_arm(False)
    run_arm(True)
    results = {"baseline": run_arm(False), "prefix_share": run_arm(True)}

    # part 2: exact pool accounting on both quantizations — N sharers
    # admitted then all retired (eos/budget AND a mid-flight cancel)
    # restore the free-count exactly; close() restores the pool fully
    accounting = {}
    shared_sys = "<sys0>" + "s" * (prefix_tokens - 7)
    for kv in (None, "int8"):
        eng = JaxEngine(
            registry={cfg.name: cfg},
            dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            paged_kv=True,
            kv_quantize=kv,
            prefix_share=True,
        )
        anchor = GenerationRequest(
            cfg.name, shared_sys + " anchor", max_new_tokens=160,
            stop_at_eos=False, seed=1,
        )
        sess = eng.decode_open([anchor], reserve_rows=8)
        sess.step(4)
        free_before = sess.pool.free_pages
        sharers = [
            GenerationRequest(
                cfg.name, shared_sys + f" q{k}", max_new_tokens=8,
                stop_at_eos=False, seed=k + 2,
            )
            for k in range(3)
        ]
        for req in sharers[:2]:
            sess.join(req)
        sess.join(sharers[2])
        sess.cancel(sharers[2])  # the cancellation path frees shared refs too
        done = 0
        while done < 2:
            done += len(sess.step(8))
        restored = sess.pool.free_pages == free_before
        total = sess.pool.n_pages
        sess.close()
        accounting["int8" if kv else "bf16"] = {
            "free_restored_after_sharers": bool(restored),
            "close_restores_pool": sess.pool.free_pages == total - 1,
        }

    line = {
        "metric": "shared_prefix",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "n_layers": cfg.n_layers,
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "budgets": list(budgets),
        "shared_prefix": {
            "frac": share_frac,
            "tokens": prefix_tokens,
            "pool": 1,
            "shared_requests": shared_requests,
        },
        "prefill_chunk_tokens": chunk_tokens,
        "decode_slice_steps": slice_steps,
        **results,
        "joiner_ttft_p50_ratio": (
            round(
                results["baseline"]["joiner_ttft_p50_s"]
                / results["prefix_share"]["joiner_ttft_p50_s"],
                2,
            )
            if results["baseline"]["joiner_ttft_p50_s"]
            and results["prefix_share"]["joiner_ttft_p50_s"]
            else None
        ),
        "computed_prefill_ratio": (
            round(
                results["prefix_share"]["prefill_tokens_computed"]
                / results["baseline"]["prefill_tokens_computed"],
                3,
            )
            if results["baseline"]["prefill_tokens_computed"]
            else None
        ),
        "pool_accounting": accounting,
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def radix_prefix_bench() -> int:
    """A/B of the ISSUE-14 persistent cross-session prefix store on a
    seeded MULTI-SESSION trace: the same requests replay through S
    session segments, each driven by a FRESH ContinuousScheduler over
    the same engine (a scheduler restart mid-trace), with a high
    shared-prefix fraction inside every segment.

    Arms (same trace, same engine shapes):
    - ``session_scoped``: prefix_store_scope="session" — the PR-7
      lifetime (the store's tree dies with each session's pool), so
      hits only happen WITHIN a segment;
    - ``engine_store``: the ISSUE-14 default — publications survive
      session close and scheduler restarts, so later segments' joiners
      hit prefixes published before the restart;
    - ``engine_store_spill``: engine scope under maximal HBM budget
      pressure (prefix_store_hbm_bytes=0) — every publication spills
      to host and every cross-session hit must RESTORE, measuring the
      hit-rate with spill pressure.

    Headlines: cross-session hit tokens (post-restart hit tokens the
    session-scoped arm cannot get), joiner TTFT p50, prefill tokens
    actually computed, and the store's hit/spill/restore counters.
    CPU-functional; RELATIVE positions are the result (docs/PERF.md
    "Persistent prefix store"). Prints ONE JSON line.
    """
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, percentile, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.prefix import (
        PREFIX_HIT_TOKENS_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.radix_store import (
        STORE_HITS_C,
        STORE_RESTORES_C,
        STORE_SPILLS_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    if not on_accelerator:
        cfg = cfg.tiny(max_seq_len=1024)
    dtype = jnp.bfloat16 if on_accelerator else jnp.float32

    sessions = int(_os.environ.get("BENCH_RP_SESSIONS", "3"))
    n_per = int(_os.environ.get("BENCH_RP_REQUESTS_PER_SESSION", "6"))
    mean_ms = float(_os.environ.get("BENCH_RP_INTERARRIVAL_MS", "25"))
    chunk_tokens = int(_os.environ.get("BENCH_RP_CHUNK_TOKENS", "64"))
    slice_steps = int(_os.environ.get("BENCH_RP_SLICE_STEPS", "8"))
    prefix_tokens = int(_os.environ.get("BENCH_RP_PREFIX_TOKENS", "192"))
    share_frac = float(_os.environ.get("BENCH_RP_SHARE_FRAC", "0.75"))
    budgets = (96, 10, 16)  # anchor outlives the arrivals (see PR-7 bench)
    segments = [
        build_workload(
            n_per,
            mean_ms / 1e3,
            seed=7 + s,
            model=cfg.name,
            budgets=budgets,
            stop_at_eos=False,
            shared_prefix_frac=share_frac,
            prefix_pool=1,
            shared_prefix_tokens=prefix_tokens,
            anchor_shared_prefix=True,
        )
        for s in range(sessions)
    ]
    all_requests = [req for seg in segments for _, req in seg]
    prompt_tokens_total = sum(len(r.prompt) + 1 for r in all_requests)

    solo_eng = JaxEngine(
        registry={cfg.name: cfg},
        dtype=dtype,
        decode_attention="auto" if on_accelerator else None,
        paged_kv=True,
    )
    solo = {id(r): solo_eng.generate(r).tokens for r in all_requests}

    def run_arm(scope: str, hbm_bytes=None):
        engine = JaxEngine(
            registry={cfg.name: cfg},
            dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            paged_kv=True,
            prefix_share=True,
            prefix_store_scope=scope,
            prefix_store_hbm_bytes=hbm_bytes,
        )
        hits_t0 = PREFIX_HIT_TOKENS_C.labels().value
        c0 = {
            "hits": STORE_HITS_C.labels().value,
            "spills": STORE_SPILLS_C.labels().value,
            "restores": STORE_RESTORES_C.labels().value,
        }
        records = []
        hit_tokens_by_segment = []
        tokens_by_req = {}
        for segment in segments:
            seg_hits0 = PREFIX_HIT_TOKENS_C.labels().value
            sched = ContinuousScheduler(
                engine,
                slice_steps=slice_steps,
                prefill_chunk_tokens=chunk_tokens,
                chunked_joins=True,
            )

            def submit(req, _s=sched):
                res = _s.submit(req)
                tokens_by_req[id(req)] = res.tokens
                return res

            sched.start()
            try:
                records.extend(run_load(submit, segment))
            finally:
                sched.stop()  # the mid-trace scheduler restart
            hit_tokens_by_segment.append(
                PREFIX_HIT_TOKENS_C.labels().value - seg_hits0
            )
        joiners = [r for r in records if r.get("joined")]
        joiner_ttfts = [
            r["ttft_s"] for r in joiners if r.get("ttft_s") is not None
        ]
        hit_tokens = PREFIX_HIT_TOKENS_C.labels().value - hits_t0
        return {
            **summarize(records),
            "joined": len(joiners),
            "joiner_ttft_p50_s": (
                round(percentile(joiner_ttfts, 50), 4)
                if joiner_ttfts
                else None
            ),
            "prefix_hit_tokens": int(hit_tokens),
            "hit_tokens_after_restart": int(
                sum(hit_tokens_by_segment[1:])
            ),
            "prefill_tokens_total": prompt_tokens_total,
            "prefill_tokens_computed": int(prompt_tokens_total - hit_tokens),
            "store_hits": int(STORE_HITS_C.labels().value - c0["hits"]),
            "store_spills": int(
                STORE_SPILLS_C.labels().value - c0["spills"]
            ),
            "store_restores": int(
                STORE_RESTORES_C.labels().value - c0["restores"]
            ),
            "parity_vs_solo": all(
                tokens_by_req.get(i) == toks for i, toks in solo.items()
            ),
        }

    run_arm("engine")  # warm every shape outside the measured arms
    results = {
        "session_scoped": run_arm("session"),
        "engine_store": run_arm("engine"),
        "engine_store_spill": run_arm("engine", hbm_bytes=0),
    }
    cross = (
        results["engine_store"]["hit_tokens_after_restart"]
        - results["session_scoped"]["hit_tokens_after_restart"]
    )
    line = {
        "metric": "radix_prefix",
        "unit": "latency_seconds",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "sessions": sessions,
        "requests_per_session": n_per,
        "shared_prefix": {"frac": share_frac, "tokens": prefix_tokens},
        **results,
        "cross_session_hit_tokens": int(cross),
        "computed_prefill_ratio": (
            round(
                results["engine_store"]["prefill_tokens_computed"]
                / results["session_scoped"]["prefill_tokens_computed"],
                3,
            )
            if results["session_scoped"]["prefill_tokens_computed"]
            else None
        ),
        "joiner_ttft_p50_ratio": (
            round(
                results["session_scoped"]["joiner_ttft_p50_s"]
                / results["engine_store"]["joiner_ttft_p50_s"],
                2,
            )
            if results["session_scoped"]["joiner_ttft_p50_s"]
            and results["engine_store"]["joiner_ttft_p50_s"]
            else None
        ),
        "spill_pressure_hit_rate": (
            round(
                results["engine_store_spill"]["store_hits"]
                / max(1, results["engine_store"]["store_hits"]),
                3,
            )
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def model_fleet_bench() -> int:
    """A/B of ISSUE-15 multi-model fleet serving on ONE seeded mixed
    trace (two tiny models — "small" and a 3×-deeper "big" — arrivals
    and per-request model assignment drawn once by the
    ``poisson_load --model-mix`` machinery, then shaped per phase).

    TTFT phase (head-of-line blocking; big-anchor shaping — request 0
    is a LONG big-model decode, the rest keep their seeded models and
    gaps):
    - ``small_solo``: only the trace's small-model requests, their own
      scheduler — the small model's UNCONTENDED TTFT reference;
    - ``serialized``: the full mixed trace through ONE model-affine
      ContinuousScheduler (the pre-ISSUE-15 shape) — small tickets
      queue behind the big model's whole session;
    - ``fleet``: the same trace through the ModelFleetScheduler —
      per-model lanes interleave decode slices under one backend lock,
      so small TTFT p99 stays within ~1.2× of solo while the
      serialized baseline blows up by multiples.

    Energy phase (the paper's headline restated ONLINE; throughput
    shaping — same arrivals/models, moderate budgets — at matched
    token output across arms):
    - ``always_big``: every request pinned to the big model (the
      "serve everything from the flagship" default);
    - ``auto_cheapest``: every request ``model:"auto"`` under
      cheapest-joules;
    - ``auto_small_first``: every request ``model:"auto"`` under the
      small-first cascade — long-budget length-cut answers ESCALATE,
      and the abandoned small-model work is COUNTED in the arm's J.

    Fleet J is accounted at the FLEET level: one chip's idle power for
    the arm's wall clock (concurrent rows share the idle window —
    summing per-row solo estimates would bill it once per row and
    penalise exactly the concurrency under test) plus each served
    token's marginal compute/HBM energy at the SERVING model's config,
    plus the escalated attempts' abandoned marginal work. Every arm
    checks per-model token parity vs solo ``generate()`` and exact
    per-model pool free-count restoration. CPU-functional; RELATIVE
    positions are the result (docs/PERF.md "Multi-model fleet
    serving"). Prints ONE JSON line.
    """
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import (
        build_workload,
        percentile,
        run_load,
        summarize,
        synth_prompt,
    )

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import (
        energy as obs_energy,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.model_fleet import (
        ModelFleetScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    dtype = jnp.bfloat16 if on_accelerator else jnp.float32
    tiny = get_model_config("qwen2:1.5b").tiny(max_seq_len=1024)
    SMALL, BIG = "tiny-small", "tiny-big"
    small_cfg = dataclasses.replace(tiny, name=SMALL)
    # the "big" model: 3× the depth and twice the FFN — ~4× the weight
    # stream, so its J/token is measurably higher and size ordering is
    # unambiguous
    big_cfg = dataclasses.replace(tiny, name=BIG, n_layers=6, d_ff=256)
    registry = {SMALL: small_cfg, BIG: big_cfg}

    n = int(_os.environ.get("BENCH_MF_REQUESTS", "7"))
    mean_ms = float(_os.environ.get("BENCH_MF_INTERARRIVAL_MS", "250"))
    small_budget = int(_os.environ.get("BENCH_MF_SMALL_BUDGET", "6"))
    anchor_budget = int(_os.environ.get("BENCH_MF_ANCHOR_BUDGET", "400"))
    small_prompt = int(_os.environ.get("BENCH_MF_SMALL_PROMPT", "256"))
    escalate_floor = int(_os.environ.get("BENCH_MF_ESCALATE_TOKENS", "32"))
    slice_steps = int(_os.environ.get("BENCH_MF_SLICE_STEPS", "1"))
    chunk_tokens = int(_os.environ.get("BENCH_MF_CHUNK_TOKENS", "32"))
    mix = {SMALL: 0.8, BIG: 0.2}
    base_trace = build_workload(
        n,
        mean_ms / 1e3,
        seed=11,
        model=SMALL,
        stop_at_eos=False,  # deterministic length-cut (the escalation
        # trigger) — no dependence on tiny random weights sampling EOS
        model_mix=mix,
    )

    def shape(budgets: "dict") -> list:
        """Shape the ONE seeded trace for a phase: request 0 becomes
        the BIG anchor (arriving 350 ms early), everyone else keeps
        their seeded model and arrival gap; smalls carry a real prefill
        (small_prompt tokens). ``budgets`` maps anchor/small/big/open
        to token budgets — the last small request is the OPEN-ENDED one
        (budget past the escalation floor) so the small-first cascade
        escalates a FRACTION of auto traffic, not all of it."""
        shaped = []
        for i, (off, req) in enumerate(base_trace):
            if i == 0:
                shaped.append(
                    (
                        0.0,
                        dataclasses.replace(
                            req,
                            model=BIG,
                            prompt=synth_prompt(128),
                            max_new_tokens=budgets["anchor"],
                        ),
                    )
                )
                continue
            if req.model == BIG:
                entry = dataclasses.replace(
                    req, max_new_tokens=budgets["big"]
                )
            else:
                entry = dataclasses.replace(
                    req,
                    prompt=synth_prompt(small_prompt) + f" q{i}",
                    max_new_tokens=budgets["small"],
                )
            shaped.append((0.35 + off, entry))
        for i in range(len(shaped) - 1, 0, -1):
            off, req = shaped[i]
            if req.model == SMALL:
                shaped[i] = (
                    off,
                    dataclasses.replace(req, max_new_tokens=budgets["open"]),
                )
                break
        return shaped

    hol_trace = shape(
        {
            "anchor": anchor_budget,
            "small": small_budget,
            "big": 24,
            "open": small_budget,
        }
    )
    # throughput shaping for the energy arms: moderate budgets so no
    # single request dominates the token mass
    energy_trace = shape(
        {"anchor": 64, "small": 24, "big": 24, "open": 48}
    )
    if not any(req.model == SMALL for _, req in hol_trace):
        raise RuntimeError("seeded mix drew no small-model requests")

    def fresh_engine() -> JaxEngine:
        return JaxEngine(
            registry=dict(registry),
            dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            paged_kv=True,
        )

    # solo references: token-parity target + the marginal-energy source
    # for abandoned (escalated) small attempts — one solo generate()
    # per (model, request shape)
    solo_eng = fresh_engine()
    solo_results: dict = {}

    def solo_for(model: str, req):
        key = (model, req.prompt, req.seed, req.max_new_tokens)
        if key not in solo_results:
            solo_results[key] = solo_eng.generate(
                dataclasses.replace(req, model=model)
            )
        return solo_results[key]

    # Energy accounting, V5E-MODELLED (the repo's roofline convention —
    # tp_continuous/spec_continuous record honest CPU walls NEXT TO the
    # v5e prediction): a depth-reduced model's CPU wall is dispatch-
    # dominated and cannot tell a 2-layer model from a 6-layer one, so
    # each request is priced by the SAME run-table energy model the
    # study uses, at the serving model's flops/bytes, over the v5e
    # bandwidth-bound duration (decode is HBM-bound: t = bytes / BW).
    # One chip serializes the fleet's compute, so per-request modelled
    # windows sum without double-counting the idle power.
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (  # noqa: E501
        generation_stats_from,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.roofline import (  # noqa: E501
        V5E_SUSTAINED_HBM_GBPS,
    )

    def modelled_j(model: str, result) -> float:
        stats = generation_stats_from(registry[model], result)
        if not stats or not stats.get("bytes"):
            return 0.0
        stats = {
            **stats,
            "duration_s": stats["bytes"] / (V5E_SUSTAINED_HBM_GBPS * 1e9),
        }
        est = obs_energy.estimate_from_stats(stats, n_chips=1)
        return float(est["J"]) if est and est.get("J") else 0.0

    def pool_restored(engine, model: str) -> bool:
        """Exact per-model pool free-count restoration: open a session,
        run every row to retirement — all row pages must be back on the
        free list (only the session's parking page stays held)."""
        sess = engine.decode_open(
            [GenerationRequest(model, "restore probe", max_new_tokens=8)]
        )
        try:
            while sess.active:
                sess.step(8)
            return sess.pool.free_pages == sess.pool.n_pages - 1
        finally:
            sess.close()

    def run_arm(
        name: str,
        arm_trace,
        policy: "str | None" = None,
        resolved_model=None,
    ):
        """One arm: a fresh engine + scheduler, the seeded trace, TTFT/
        throughput records, fleet-level Joules and the parity/
        restoration checks. ``resolved_model(req)`` maps each request
        to the model expected to SERVE it (parity target); None = the
        request's own model."""
        engine = fresh_engine()
        if policy is not None:
            sched = ModelFleetScheduler(
                engine,
                models=[SMALL, BIG],
                model_policy=policy,
                escalate_max_tokens=escalate_floor,
                slice_steps=slice_steps,
                prefill_chunk_tokens=chunk_tokens,
            )
        else:
            sched = ContinuousScheduler(
                engine,
                slice_steps=slice_steps,
                prefill_chunk_tokens=chunk_tokens,
            )
        results: dict = {}

        def submit(req, _s=sched):
            res = _s.submit(req)
            results[id(req)] = res
            return res

        sched.start()
        t_arm0 = time.monotonic()
        try:
            records = run_load(submit, arm_trace)
        finally:
            arm_wall_s = time.monotonic() - t_arm0
            sched.stop()
        served_j = 0.0
        abandoned_j = 0.0
        tokens = 0
        parity = True
        for _off, req in arm_trace:
            res = results.get(id(req))
            if res is None:
                parity = False
                continue
            served = res.request.model
            expect = resolved_model(req) if resolved_model else req.model
            if served != expect:
                parity = False
            if res.tokens != solo_for(served, req).tokens:
                parity = False
            tokens += res.generated_tokens
            served_j += modelled_j(served, res)
            fleet_extras = (res.extras or {}).get("fleet", {})
            if fleet_extras.get("escalated"):
                # the abandoned small attempt decoded exactly what a
                # solo small run of this request decodes — its modelled
                # window is charged to the arm too
                frm = fleet_extras["escalated_from"]
                abandoned_j += modelled_j(frm, solo_for(frm, req))
        fleet_j = served_j + abandoned_j
        small_ttfts = [
            r["ttft_s"]
            for r in records
            if r.get("model") == SMALL and r.get("ttft_s") is not None
        ]
        out = {
            **summarize(records),
            "small_ttft_p99_s": (
                round(percentile(small_ttfts, 99), 4)
                if small_ttfts
                else None
            ),
            "wall_s": round(arm_wall_s, 3),
            "v5e_served_J": round(served_j, 6),
            "v5e_abandoned_escalation_J": round(abandoned_j, 6),
            "fleet_J": round(fleet_j, 6),
            "fleet_J_per_token": (
                round(fleet_j / tokens, 9) if tokens else None
            ),
            "parity_vs_solo": parity,
            "pool_restored": {
                m: pool_restored(engine, m) for m in (SMALL, BIG)
            },
        }
        return out

    small_only = [
        (off, req) for off, req in hol_trace if req.model == SMALL
    ]
    # energy arms: EVERYTHING asks for model:"auto" (vs the always-big
    # single-model default) — the acceptance A/B at matched budgets
    auto_energy = [
        (off, dataclasses.replace(req, model="auto"))
        for off, req in energy_trace
    ]
    big_energy = [
        (off, dataclasses.replace(req, model=BIG))
        for off, req in energy_trace
    ]

    def small_first_resolved(req):
        # deterministic cascade outcome: every answer is length-cut
        # (stop_at_eos=False), so auto requests at/above the floor
        # escalate; named requests serve where they asked
        if req.model != "auto":
            return req.model
        return BIG if req.max_new_tokens >= escalate_floor else SMALL

    def cheapest_resolved(req):
        return SMALL if req.model == "auto" else req.model

    # compile every shape outside the measured arms
    run_arm("warm_fleet", hol_trace, policy="small-first")
    run_arm("warm_serialized", hol_trace)
    run_arm(
        "warm_auto",
        auto_energy,
        policy="small-first",
        resolved_model=small_first_resolved,
    )
    run_arm("warm_big", big_energy)
    arms = {
        "small_solo": run_arm("small_solo", small_only),
        "serialized": run_arm("serialized", hol_trace),
        "fleet": run_arm("fleet", hol_trace, policy="small-first"),
        "always_big": run_arm("always_big", big_energy),
        "auto_cheapest": run_arm(
            "auto_cheapest",
            auto_energy,
            policy="cheapest-joules",
            resolved_model=cheapest_resolved,
        ),
        "auto_small_first": run_arm(
            "auto_small_first",
            auto_energy,
            policy="small-first",
            resolved_model=small_first_resolved,
        ),
    }
    solo_p99 = arms["small_solo"]["small_ttft_p99_s"]

    def ratio(a, b):
        return (
            round(a / b, 3)
            if a is not None and b not in (None, 0)
            else None
        )

    fleet_vs_solo = ratio(arms["fleet"]["small_ttft_p99_s"], solo_p99)
    line = {
        "metric": "model_fleet",
        "unit": "latency_seconds",
        "models": {SMALL: "2L/d64", BIG: "6L/d64/ff256"},
        "backend": jax.default_backend(),
        "requests": n,
        "model_mix": mix,
        "escalate_max_tokens": escalate_floor,
        **arms,
        # (a) head-of-line blocking: fleet small TTFT p99 vs its solo
        # figure (target ≤ ~1.2×) next to the serialized baseline's
        # multiple-× blowup on the SAME trace
        "small_ttft_p99_fleet_vs_solo": fleet_vs_solo,
        "small_ttft_p99_serialized_vs_solo": ratio(
            arms["serialized"]["small_ttft_p99_s"], solo_p99
        ),
        "no_hol_blocking": bool(
            fleet_vs_solo is not None
            and fleet_vs_solo
            <= float(_os.environ.get("BENCH_MF_HOL_FACTOR", "1.2"))
        ),
        # (b) the paper's headline online: auto-routing fleet J/token
        # vs always-big single-model at matched token output
        # (escalation's abandoned work INCLUDED in the auto arms' J)
        "j_per_token_cheapest_vs_always_big": ratio(
            arms["auto_cheapest"]["fleet_J_per_token"],
            arms["always_big"]["fleet_J_per_token"],
        ),
        "j_per_token_small_first_vs_always_big": ratio(
            arms["auto_small_first"]["fleet_J_per_token"],
            arms["always_big"]["fleet_J_per_token"],
        ),
        "escalations": arms["auto_small_first"].get("escalations", 0),
        "parity_all_arms": all(a["parity_vs_solo"] for a in arms.values()),
        "pools_restored_all_arms": all(
            all(a["pool_restored"].values()) for a in arms.values()
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def _tp_continuous_arm(n_devices: int) -> int:
    """ONE arm of the tp_continuous A/B, run in its own process (the
    parent pins ``xla_force_host_platform_device_count`` in XLA_FLAGS —
    a device count is a process-lifetime property, so each arm needs a
    fresh interpreter). Serves a seeded Poisson trace through the
    continuous scheduler on an ``n_devices`` TP mesh, plus a CONTROLLED
    fixed-occupancy slice-timing phase whose per-step wall is what the
    1→n ratio is computed from. Prints ONE JSON line."""
    import os as _os
    import statistics as _stats
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    if len(jax.devices()) < n_devices:
        print(json.dumps({"error": f"need {n_devices} devices, have {len(jax.devices())}"}))
        return 1
    # tiny config whose 8 KV heads divide both mesh sizes — the SPMD
    # program shape (heads-sharded pool, replicated row control) is the
    # real one; only the arithmetic is CPU-sized
    cfg = dataclasses.replace(
        get_model_config("qwen2:1.5b").tiny(),
        n_heads=8, n_kv_heads=8, d_ff=128, d_model=64, d_head=16,
        max_seq_len=1024,
    )
    mesh = build_mesh(MeshSpec.tp_only(), devices=jax.devices()[:n_devices])
    engine = TensorParallelEngine(
        mesh=mesh,
        registry={cfg.name: cfg},
        dtype=jnp.float32,
        paged_kv=True,
    )
    slice_steps = 8
    rows = int(_os.environ.get("BENCH_TPC_ROWS", "8"))
    budget = 64

    # -- controlled phase: fixed occupancy, measured per-slice walls ------
    fleet = [
        GenerationRequest(
            cfg.name, f"row {i} holds its slot", max_new_tokens=budget,
            stop_at_eos=False, seed=100 + i,
        )
        for i in range(rows)
    ]
    solo = [engine.generate(r) for r in fleet]  # also warms every shape
    sess = engine.decode_open(
        fleet, reserve_rows=rows, slice_steps=slice_steps
    )
    sess.step(slice_steps)  # first slice pays any residual compile
    slice_walls = []
    results = []
    while sess.active:
        full = sess.active == rows
        t0 = time.monotonic()
        retired = sess.step(slice_steps)
        if full and sess.active == rows:  # full-occupancy slices only
            slice_walls.append(time.monotonic() - t0)
        results.extend(retired)
    parity = all(
        got.tokens == ref.tokens
        for ref, got in zip(
            solo,
            sorted(results, key=lambda r: fleet.index(r.request)),
        )
    )
    sess.close()
    mean_slice = _stats.mean(slice_walls) if slice_walls else None
    controlled = {
        "rows": rows,
        "slice_steps": slice_steps,
        "full_occupancy_slices": len(slice_walls),
        "mean_slice_s": round(mean_slice, 6) if mean_slice else None,
        "mean_step_s": (
            round(mean_slice / slice_steps, 6) if mean_slice else None
        ),
        "p95_slice_s": (
            round(sorted(slice_walls)[int(0.95 * (len(slice_walls) - 1))], 6)
            if slice_walls
            else None
        ),
    }

    # -- served phase: Poisson trace through the continuous scheduler -----
    n = int(_os.environ.get("BENCH_TPC_REQUESTS", "12"))
    mean_ms = float(_os.environ.get("BENCH_TPC_INTERARRIVAL_MS", "50"))
    workload = build_workload(
        n, mean_ms / 1e3, seed=11, model=cfg.name,
        budgets=(8, 16, 48),
        prompts=("alpha beta", "gamma delta epsilon", "zeta eta"),
        stop_at_eos=False,
    )
    for req in {r.max_new_tokens: r for _, r in workload}.values():
        engine.generate(req)  # warm the trace's buckets outside timing
    sched = ContinuousScheduler(engine, slice_steps=slice_steps)
    sched.start()
    try:
        records = run_load(sched.submit, workload)
    finally:
        sched.stop()
    poisson = summarize(records)

    # per-slice step-time breakdown as the flight recorder saw it: every
    # slice of BOTH phases, with rows + duration (forensics twin of the
    # controlled figure)
    slice_events = []
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.flight import (
            FLIGHT,
        )

        slice_events = [
            {"rows": e.get("rows"), "dur_s": e.get("dur_s")}
            for e in FLIGHT.events(n=4096, type_="slice")
        ]
    except Exception:
        pass

    line = {
        "arm": "tp_continuous",
        "devices": n_devices,
        "mesh": engine.mesh_info(),
        "backend": jax.default_backend(),
        "model": cfg.name,
        "kv_heads_sharded": cfg.n_kv_heads % n_devices == 0
        and n_devices > 1,
        "parity_vs_solo": parity,
        "controlled": controlled,
        "poisson": poisson,
        "sched_slice_events": len(slice_events),
        "slice_time_by_rows": _slice_breakdown(slice_events),
    }
    print(json.dumps(line))
    return 0


def _slice_breakdown(slice_events) -> dict:
    """Group flight slice events by row count → {rows: {n, mean_s}}."""
    import statistics as _stats

    by_rows = {}
    for e in slice_events:
        if e.get("dur_s") is None:
            continue
        by_rows.setdefault(e.get("rows"), []).append(e["dur_s"])
    return {
        str(rows): {"n": len(ds), "mean_s": round(_stats.mean(ds), 6)}
        for rows, ds in sorted(
            by_rows.items(), key=lambda kv: (kv[0] is None, kv[0])
        )
    }


def spec_continuous_bench() -> int:
    """A/B of BATCHED speculative decoding inside the continuous
    scheduler (ISSUE 9) at 1/8/32-row Poisson traces: per arm the SAME
    seeded trace of greedy requests drives a ContinuousScheduler over a
    plain tiny engine and over one with an acceptance-friendly draft
    (the draft registry entry aliases the target config, so seeded init
    gives identical weights — every proposal is accepted, the upper
    bound of the Leviathan-style amortization the mode exists for;
    acceptance-hostile drafts are covered by the fallback tests).

    Reported per row count: aggregate tok/s both arms, the speculative
    arm's measured TOKENS-PER-TARGET-STEP (each retired row's decode
    tokens / its draft-verify rounds — 1.0 by definition in the plain
    arm; > 1.0 is the acceptance criterion), bit-exact parity of the
    two arms' token streams (both must be the target's greedy stream),
    and exact pool free-count restoration after join + cancel + close
    on bf16 AND int8 paged pools. The PAGED-NATIVE arm (ISSUE 10)
    records pages-billed-per-spec-row — native (slack-free) vs the
    retired legacy ``2k+2``-slack formula — and max-admission-rows at
    equal HBM budget for a spec vs a plain engine (the no-admission-tax
    acceptance criterion: spec ≥ plain). NEXT TO the
    measured CPU-functional numbers sits the v5e ROOFLINE column: the
    modelled speedup E[m]/(1 + k·c) for the paper's serving config
    (qwen2:1.5b int8 weights, ctx 512) with a ¼-depth self-draft
    (c = modelled draft/target step-time ratio), at the measured
    acceptance and at a conservative α=0.7 — the number a real-slice
    run should approach. Prints ONE JSON line."""
    import dataclasses as _dc
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    cfg = _dc.replace(
        cfg.tiny(max_seq_len=1024) if not on_accelerator else cfg,
        name="tiny-spec-target",
    )
    spec_k = int(_os.environ.get("BENCH_SPEC_K", "4"))
    registry = {"tiny-spec-target": cfg, "tiny-spec-draft": cfg}
    dtype = jnp.bfloat16 if on_accelerator else jnp.float32

    def make_engine(spec: bool) -> JaxEngine:
        return JaxEngine(
            registry=dict(registry),
            dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            speculative=(
                {"tiny-spec-target": ("tiny-spec-draft", spec_k)}
                if spec
                else None
            ),
        )

    budgets = (16, 32, 48)
    prompts = ("alpha beta", "gamma delta epsilon", "zeta eta")
    mean_ms = float(_os.environ.get("BENCH_SPEC_INTERARRIVAL_MS", "30"))

    arms = {}
    for rows in (1, 8, 32):
        workload = build_workload(
            rows, mean_ms / 1e3, seed=11, model=cfg.name,
            budgets=budgets, prompts=prompts, stop_at_eos=False,
        )
        per_rows = {}
        tokens_by_req = {}
        for arm in ("plain", "speculative"):
            engine = make_engine(arm == "speculative")
            # warm every compiled shape outside the measured trace
            warm = [req for _, req in workload[: min(rows, 6)]]
            sess = engine.decode_open(warm, reserve_rows=2 * len(warm))
            while sess.active:
                sess.step()
            sess.close()
            sched = ContinuousScheduler(engine)
            sched.start()
            results = []

            def submit(req, _sched=sched, _sink=results):
                res = _sched.submit(req)
                _sink.append(res)
                return res

            try:
                records = run_load(submit, workload)
            finally:
                sched.stop()
            summary = summarize(records)
            tokens_by_req[arm] = {
                f"{r.request.prompt}|{r.request.seed}"
                f"|{r.request.max_new_tokens}": r.tokens
                for r in results
            }
            tpts = None
            if arm == "speculative":
                per_row_ratios = [
                    (r.generated_tokens - 1) / r.extras["spec"]["rounds"]
                    for r in results
                    if (r.extras or {}).get("spec", {}).get("rounds")
                ]
                tpts = (
                    round(sum(per_row_ratios) / len(per_row_ratios), 3)
                    if per_row_ratios
                    else None
                )
            per_rows[arm] = {
                "agg_tokens_per_s": summary.get("agg_tokens_per_s"),
                "completion_p50_s": summary.get("completion_p50_s"),
                "tokens_per_target_step": tpts if tpts else (
                    1.0 if arm == "plain" else None
                ),
            }
        per_rows["parity_spec_vs_plain"] = (
            tokens_by_req["plain"] == tokens_by_req["speculative"]
        )
        arms[str(rows)] = per_rows

    # exact pool free-count restoration after join + cancel + retire +
    # close, on bf16 AND int8 paged pools — plus the ISSUE-10 paged-
    # native billing A/B: pages-billed-per-spec-row native vs the
    # retired legacy slack formula, and max-admission-rows at equal HBM
    # budget spec vs plain (no spec admission tax)
    restoration = {}
    paged_native = {}
    page = 128
    for kv in (None, "int8"):
        eng = JaxEngine(
            registry=dict(registry), dtype=dtype, paged_kv=True,
            kv_quantize=kv,
            decode_attention="auto" if on_accelerator else None,
            speculative={"tiny-spec-target": ("tiny-spec-draft", spec_k)},
        )
        plain_paged = JaxEngine(
            registry=dict(registry), dtype=dtype, paged_kv=True,
            kv_quantize=kv,
            decode_attention="auto" if on_accelerator else None,
        )
        # budgets sized so the anchor is STILL live across the join +
        # cancel (spec rounds advance ~k+1 tokens per step at full
        # acceptance — a short anchor would retire mid-check and return
        # its own pages, muddying the exactness assertion)
        anchor = GenerationRequest(
            cfg.name, "pool anchor", max_new_tokens=200, stop_at_eos=False
        )
        victim = GenerationRequest(
            cfg.name, "victim", max_new_tokens=150, stop_at_eos=False, seed=3
        )
        sess = eng.decode_open([anchor], reserve_rows=4)
        ok = sess.spec is not None
        # slack-free billing: the session's sizing rule bills a spec row
        # EXACTLY the plain-decode page count
        # the legacy column is the RETIRED rule: pre-ISSUE-10 spec rows
        # were excluded from stacked mode and billed prompt + budget +
        # 2k+2 slack through the table
        s_probe, mnt_probe = 100, 150
        native_pages = sess._pages_needed(s_probe, mnt_probe)
        legacy_pages = -(-(s_probe + mnt_probe + 2 * spec_k + 2) // page)
        plain_sess = plain_paged.decode_open([anchor], reserve_rows=2)
        ok = ok and native_pages == plain_sess._pages_needed(
            s_probe, mnt_probe
        )
        plain_sess.close()
        admission_req = GenerationRequest(
            cfg.name, "admission probe", max_new_tokens=mnt_probe,
            stop_at_eos=False,
        )
        adm_spec = eng.max_admission_rows(admission_req)
        adm_plain = plain_paged.max_admission_rows(admission_req)
        paged_native["bf16" if kv is None else "int8"] = {
            "pages_per_spec_row_native": int(native_pages),
            "pages_per_spec_row_legacy_formula": int(legacy_pages),
            "verify_mode": sess._verify_mode(),
            "max_admission_rows_spec": int(adm_spec),
            "max_admission_rows_plain": int(adm_plain),
            "no_spec_admission_tax": bool(adm_spec >= adm_plain),
        }
        free0 = sess.pool.free_pages
        sess.step(2)
        sess.join(victim)
        sess.step(2)
        ok = ok and sess.active == 2  # both rows still live
        ok = ok and sess.cancel(victim) and sess.pool.free_pages == free0
        while sess.active:
            sess.step()
        sess.close()
        ok = ok and sess.pool.free_pages == sess.pool.n_pages - 1
        restoration["bf16" if kv is None else "int8"] = bool(ok)

    # v5e roofline column: modelled speedup for the paper's serving
    # config with a ¼-depth self-draft
    roofline = None
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.roofline import (
            modeled_tp_decode_step_s,
        )

        full = get_model_config("qwen2:1.5b")
        draft_full = _dc.replace(full, n_layers=max(1, full.n_layers // 4))
        ctx = 512
        t_target = modeled_tp_decode_step_s(full, "int8", 1, ctx)
        c = modeled_tp_decode_step_s(draft_full, "int8", 1, ctx) / t_target

        def expected_m(alpha: float) -> float:
            if alpha >= 1.0:
                return spec_k + 1
            return (1 - alpha ** (spec_k + 1)) / (1 - alpha)

        measured_alpha = 1.0  # the acceptance-friendly draft accepts all
        roofline = {
            "config": "qwen2:1.5b int8 ctx512, draft=quarter-depth self",
            "draft_cost_ratio_c": round(c, 4),
            "k": spec_k,
            "predicted_speedup_at_measured_alpha": round(
                expected_m(measured_alpha) / (1 + spec_k * c), 3
            ),
            "predicted_speedup_at_alpha_0p7": round(
                expected_m(0.7) / (1 + spec_k * c), 3
            ),
        }
    except Exception:
        pass

    line = {
        "metric": "spec_continuous",
        "unit": "tokens_per_target_step",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "k": spec_k,
        "arms_by_rows": arms,
        "pool_restoration_exact": restoration,
        "paged_native_billing": paged_native,
        "roofline_v5e": roofline,
        "note": (
            "CPU-functional figures measure the MECHANICS (per-row "
            "variable-stride acceptance, parity, pool accounting); the "
            "wall-clock win needs real HBM bandwidth — the roofline "
            "column is what a v5e run should approach"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def spec_sampled_bench() -> int:
    """Sampled speculative decoding (ISSUE 16): measured
    TOKENS-PER-TARGET-STEP at temperature 0.7 across 3 content lengths
    × the three draft sources — model-draft (acceptance-friendly
    aliased draft: q = p, every proposal accepted — the rejection-
    resampling upper bound), n-gram prompt-lookup (real acceptance on
    repetitive content, zero extra weights), and cross-model (another
    lane's resident model as draft). Each retired row contributes
    (decode tokens − 1) / rounds; > 1 means sampled traffic amortizes
    target steps exactly like greedy traffic did pre-ISSUE-16 — the
    population the greedy-only gate previously excluded entirely.

    The FLEET column prices cross-model drafting in the paper's unit of
    account: v5e-modelled J/token of big+small-draft speculation vs
    big-solo plain decode (qwen2:1.5b int8 ctx512 target, quarter-depth
    small draft; decode is HBM-bound so a step's energy is its modelled
    wall × (idle + HBM-active) W). Fleet J/token = solo × (1 + k·c) /
    E[m] — the acceptance criterion is fleet < solo at the measured
    per-round acceptance. Prints ONE JSON line."""
    import dataclasses as _dc
    import os as _os

    import jax
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    on_accelerator = on_tpu()
    cfg = get_model_config("qwen2:1.5b")
    cfg = _dc.replace(
        cfg.tiny(max_seq_len=1024) if not on_accelerator else cfg,
        name="tiny-spec-target",
    )
    spec_k = int(_os.environ.get("BENCH_SPEC_K", "4"))
    registry = {"tiny-spec-target": cfg, "tiny-spec-draft": cfg}
    dtype = jnp.bfloat16 if on_accelerator else jnp.float32
    temperature = 0.7

    # three content lengths: repetitive prompts of growing history (the
    # n-gram source's acceptance is a function of lookup-able content;
    # the model sources are length-insensitive by construction)
    lengths = {
        "short": "the quick brown fox " * 2,
        "medium": "the quick brown fox jumps over the lazy dog " * 4,
        "long": "the quick brown fox jumps over the lazy dog " * 10,
    }
    sources = {
        "model": ("tiny-spec-draft", spec_k),
        "ngram": ("ngram", spec_k),
        "cross": ("cross:tiny-spec-draft", spec_k),
    }
    rows_per_cell = int(_os.environ.get("BENCH_SPEC_SAMPLED_ROWS", "8"))
    budget = int(_os.environ.get("BENCH_SPEC_SAMPLED_TOKENS", "64"))

    by_source = {}
    measured_alpha = {}
    for source, spec in sources.items():
        eng = JaxEngine(
            registry=dict(registry), dtype=dtype,
            decode_attention="auto" if on_accelerator else None,
            speculative={"tiny-spec-target": spec},
        )
        cells = {}
        acc_tot = drafted_tot = 0
        for label, prompt in lengths.items():
            reqs = [
                GenerationRequest(
                    "tiny-spec-target", prompt, max_new_tokens=budget,
                    temperature=temperature, seed=100 + i,
                    stop_at_eos=False,
                )
                for i in range(rows_per_cell)
            ]
            sess = eng.decode_open(reqs)
            results = []
            while sess.active:
                results.extend(sess.step(16))
            sess.close()
            ratios, acc, drafted = [], 0, 0
            for r in results:
                sx = (r.extras or {}).get("spec") or {}
                if sx.get("rounds"):
                    ratios.append(
                        (r.generated_tokens - 1) / sx["rounds"]
                    )
                    acc += sx.get("accepted", 0)
                    drafted += sx.get("drafted", 0)
            cells[label] = {
                "tokens_per_target_step": (
                    round(sum(ratios) / len(ratios), 3) if ratios else None
                ),
                "acceptance": (
                    round(acc / drafted, 3) if drafted else None
                ),
            }
            acc_tot += acc
            drafted_tot += drafted
        tpts_all = [
            c["tokens_per_target_step"]
            for c in cells.values()
            if c["tokens_per_target_step"]
        ]
        by_source[source] = {
            **cells,
            "mean_tokens_per_target_step": (
                round(sum(tpts_all) / len(tpts_all), 3) if tpts_all else None
            ),
        }
        measured_alpha[source] = (
            acc_tot / drafted_tot if drafted_tot else 0.0
        )

    # v5e-modelled fleet J/token: big + small-draft speculation vs
    # big-solo plain decode, priced at the HBM-bound decode power point
    fleet = None
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.roofline import (
            modeled_tp_decode_step_s,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
            V5E_HBM_ACTIVE_W,
            V5E_IDLE_W,
        )

        big = get_model_config("qwen2:1.5b")
        small = _dc.replace(big, n_layers=max(1, big.n_layers // 4))
        ctx = 512
        t_big = modeled_tp_decode_step_s(big, "int8", 1, ctx)
        t_small = modeled_tp_decode_step_s(small, "int8", 1, ctx)
        c = t_small / t_big
        watts = V5E_IDLE_W + V5E_HBM_ACTIVE_W
        solo_jpt = t_big * watts

        def expected_m(alpha: float) -> float:
            if alpha >= 1.0:
                return float(spec_k + 1)
            return (1 - alpha ** (spec_k + 1)) / (1 - alpha)

        # the per-round acceptance probability the cross arm measured:
        # accepted/drafted is the mean fraction of k accepted, a
        # conservative stand-in for the geometric alpha
        alpha = measured_alpha["cross"]
        e_m = expected_m(alpha)
        fleet_jpt = solo_jpt * (1 + spec_k * c) / e_m
        fleet = {
            "config": (
                "qwen2:1.5b int8 ctx512 target, quarter-depth small draft"
            ),
            "power_point_W": watts,
            "draft_cost_ratio_c": round(c, 4),
            "k": spec_k,
            "measured_cross_acceptance": round(alpha, 3),
            "expected_tokens_per_round": round(e_m, 3),
            "solo_big_J_per_token": round(solo_jpt, 6),
            "fleet_spec_J_per_token": round(fleet_jpt, 6),
            "fleet_beats_solo": bool(fleet_jpt < solo_jpt),
        }
    except Exception:
        pass

    line = {
        "metric": "spec_sampled",
        "unit": "tokens_per_target_step",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "k": spec_k,
        "temperature": temperature,
        "rows_per_cell": rows_per_cell,
        "budget": budget,
        "by_source": by_source,
        "fleet_energy_v5e": fleet,
        "note": (
            "CPU-functional figures measure the sampled-acceptance "
            "MECHANICS (rejection resampling's per-row stride); the "
            "model/cross arms alias draft and target configs (q = p, "
            "acceptance -> 1 — the amortization ceiling), the ngram "
            "arm shows real prompt-lookup acceptance on repetitive "
            "content; the fleet column is the v5e-modelled J/token "
            "a real-slice run should approach"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def tp_continuous_bench() -> int:
    """Poisson A/B of the continuous scheduler on a 1-device vs a
    forced-host 8-device TP mesh (ISSUE 8): the stepped carry is an
    explicitly-sharded SPMD pytree, so the SAME scheduler loop drives
    both arms — each arm runs in its own interpreter because the
    virtual device count is fixed at process start
    (``--xla_force_host_platform_device_count``).

    The headline figure is the measured 1→8 per-step wall ratio at
    fixed occupancy, recorded NEXT TO the roofline model's predicted
    v5e ratio (parallel/roofline.py — the AOT-validated 2.1–4.8×
    modelled 8-chip speedups this PR makes servable). On the CPU dev
    environment the measured ratio is an SPMD-OVERHEAD figure (8
    virtual devices share one CPU's bandwidth; expect ≤1×) — the bench
    exists so the identical entry run on a real slice fills in the
    hardware column, and so CPU regressions in the sharded step path
    are visible per-slice. Prints ONE JSON line."""
    import os as _os
    import subprocess as _sp

    arms = {}
    for n_dev in (1, 8):
        env = dict(_os.environ)
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu") or "cpu"
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_dev}")
        env["XLA_FLAGS"] = " ".join(flags)
        proc = _sp.run(
            [sys.executable, _os.path.abspath(__file__),
             "_tp_continuous_arm", str(n_dev)],
            capture_output=True, text=True, env=env,
            cwd=_os.path.dirname(_os.path.abspath(__file__)),
            timeout=1800,
        )
        last = (proc.stdout.strip().splitlines() or ["{}"])[-1]
        try:
            arms[n_dev] = json.loads(last)
        except json.JSONDecodeError:
            arms[n_dev] = {
                "error": f"arm {n_dev} emitted no JSON",
                "stdout_tail": proc.stdout[-500:],
                "stderr_tail": proc.stderr[-500:],
            }
        if proc.returncode != 0 and "error" not in arms[n_dev]:
            arms[n_dev]["error"] = f"exit {proc.returncode}"

    def step_s(arm):
        return ((arm.get("controlled") or {}).get("mean_step_s")) or None

    s1, s8 = step_s(arms.get(1, {})), step_s(arms.get(8, {}))
    measured_ratio = round(s1 / s8, 3) if s1 and s8 else None

    # The roofline's prediction for the PAPER's serving config (qwen2:
    # 1.5b int8 weights, v5e sustained bandwidth) at the study's
    # mid-context — the number the measured ratio should approach when
    # this same entry runs on a real 8-chip slice.
    predicted_ratio = None
    try:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
            get_model_config,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.roofline import (
            modeled_tp_decode_step_s,
        )

        full = get_model_config("qwen2:1.5b")
        ctx = 512
        predicted_ratio = round(
            modeled_tp_decode_step_s(full, "int8", 1, ctx)
            / modeled_tp_decode_step_s(full, "int8", 8, ctx),
            3,
        )
    except Exception:
        pass

    line = {
        "metric": "tp_continuous",
        "unit": "step_time_ratio",
        "arms": {str(k): v for k, v in arms.items()},
        "measured_step_ratio_1_to_8": measured_ratio,
        "roofline_predicted_ratio_1_to_8_v5e": predicted_ratio,
        "note": (
            "measured ratio is forced-host CPU SPMD overhead unless run "
            "on a real slice; predicted ratio is the v5e roofline "
            "(docs/roofline_aot.json validates its structural terms)"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def router_fleet_bench() -> int:
    """Replica-fleet routing A/B (ISSUE 12): aggregate tok/s + TTFT p99
    of 1 vs 2 vs 4 FakeBackend replicas behind the front-door router
    (serve/router.py) on Poisson traces at 1×/2×/4× the SINGLE-replica
    saturating rate, least-queue vs round-robin dispatch arms.

    The fake replica is a calibrated capacity model: with
    ``simulate_delay`` a decode slice of k steps sleeps k/tokens_per_s
    once for ALL live rows (the shared-window semantics of a real
    batched decode), so one replica's ceiling is tokens_per_s ×
    max_rows — the HBM-bound admission cap's stand-in. Overload beyond
    one ceiling can ONLY be served by more replicas, which is exactly
    the router's claim: aggregate tok/s ≥1.8× at 2 replicas (≥3.2× at
    4) on the 2×/4× traces, with fleet TTFT p99 at 1× load no worse
    than the single replica's. Prints ONE JSON line."""
    import os
    import sys as _sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from scripts.poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.router import (
        LocalReplica,
        Router,
    )

    TOKENS_PER_S = 400.0  # per-replica decode rate (fake, shared window)
    MAX_ROWS = 8  # per-replica admission ceiling (the HBM stand-in)
    capacity = TOKENS_PER_S * MAX_ROWS  # one replica's tok/s ceiling
    BUDGETS = (48, 96, 160)
    mean_tokens = sum(BUDGETS) / len(BUDGETS)

    def run_arm(n_replicas: int, policy: str, load_x: float, n: int):
        """One (fleet size, policy, load multiple) arm over the SAME
        seeded trace family: mean inter-arrival is scaled so offered
        token demand is load_x × one replica's ceiling."""
        interarrival_s = mean_tokens / (capacity * load_x)
        workload = build_workload(
            n,
            interarrival_s,
            seed=7,
            model="bench:fleet",
            budgets=list(BUDGETS),
            stop_at_eos=False,
        )
        replicas = [
            LocalReplica(
                f"r{i}",
                FakeBackend(
                    tokens_per_s=TOKENS_PER_S,
                    simulate_delay=True,
                    max_rows=MAX_ROWS,
                ),
            )
            for i in range(n_replicas)
        ]
        router = Router(replicas, policy=policy, probe_interval_s=0.25)
        router.start()
        try:
            records = run_load(router.dispatch, workload)
        finally:
            router.stop()
        summary = summarize(records)
        return {
            "replicas": n_replicas,
            "policy": policy,
            "load_x": load_x,
            "requests": n,
            "agg_tokens_per_s": summary.get("agg_tokens_per_s"),
            "ttft_p50_s": summary.get("ttft_p50_s"),
            "ttft_p99_s": summary.get("ttft_p99_s"),
            "completion_p95_s": summary.get("completion_p95_s"),
            "errors": summary.get("errors"),
            "per_replica": summary.get("replicas"),
        }

    arms = {
        # TTFT reference at 1×: the fleet's front door must not tax the
        # un-overloaded case
        "single_1x": run_arm(1, "least-queue", 1.0, 64),
        "fleet2_1x_least_queue": run_arm(2, "least-queue", 1.0, 64),
        # the single replica is saturated 2×/4× over; only more
        # replicas can serve the offered load
        "single_2x": run_arm(1, "least-queue", 2.0, 128),
        "fleet2_2x_least_queue": run_arm(2, "least-queue", 2.0, 128),
        "fleet2_2x_round_robin": run_arm(2, "round-robin", 2.0, 128),
        "single_4x": run_arm(1, "least-queue", 4.0, 192),
        "fleet4_4x_least_queue": run_arm(4, "least-queue", 4.0, 192),
        "fleet4_4x_round_robin": run_arm(4, "round-robin", 4.0, 192),
    }

    def ratio(a, b):
        va, vb = arms[a]["agg_tokens_per_s"], arms[b]["agg_tokens_per_s"]
        return round(va / vb, 3) if va and vb else None

    line = {
        "metric": "router_fleet",
        "unit": "agg_tokens_per_s",
        "replica_model": {
            "tokens_per_s": TOKENS_PER_S,
            "max_rows": MAX_ROWS,
            "ceiling_tokens_per_s": capacity,
        },
        "arms": arms,
        "speedup_2_replicas_at_2x": ratio(
            "fleet2_2x_least_queue", "single_2x"
        ),
        "speedup_4_replicas_at_4x": ratio(
            "fleet4_4x_least_queue", "single_4x"
        ),
        "least_queue_vs_round_robin_2x": ratio(
            "fleet2_2x_least_queue", "fleet2_2x_round_robin"
        ),
        "ttft_p99_fleet_vs_single_at_1x": (
            round(
                arms["fleet2_1x_least_queue"]["ttft_p99_s"]
                / arms["single_1x"]["ttft_p99_s"],
                3,
            )
            if arms["single_1x"].get("ttft_p99_s")
            and arms["fleet2_1x_least_queue"].get("ttft_p99_s")
            else None
        ),
        "note": (
            "fake replicas are calibrated capacity models "
            "(tokens_per_s x max_rows ceiling); the figures measure the "
            "ROUTER's scaling/dispatch quality, not engine speed — on "
            "real engines each replica is one mesh/host (serve-fleet "
            "--targets)"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    _sys.stdout.flush()
    return 0


def affinity_routing_bench() -> int:
    """Prefix-affinity fleet routing A/B (ISSUE 19): the SAME seeded
    75%-shared-prefix Poisson trace (two distinct 192-token system
    prompts, ``scripts/poisson_load.py --shared-prefix-frac 0.75
    --prefix-pool 2``) served by a 2-replica prefix-sharing fake fleet
    under ``--route-policy affinity`` vs ``least-queue``.

    Each fake replica owns a budget-capped cross-session prefix store
    (32 KiB HBM ≈ TWO recent entries, zero host tier), so the fleet
    keeps store locality ONLY if the router keeps sending a family to
    the replica whose store is warm on it. Affinity does exactly that —
    the probes carry bounded radix digests and the probe-side estimator
    scores the request's chunk hashes against them — while least-queue
    interleaves both families across both replicas and thrashes the
    stores. Two figures ride the headline: fleet TTFT p99 (a store hit
    prefills only the divergent tail, so the chunked join's wall
    shrinks) and PREFILL COMPUTED TOKENS (total prompt tokens minus the
    llm_prefix_hit_tokens_total delta — the recompute the paper's
    J/request story bills). Decode token parity between the arms is
    asserted structurally: the seeded trace replays exactly, budgets
    are fixed, so both arms must stream the same token totals. Prints
    ONE JSON line."""
    import os
    import sys as _sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from scripts.poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.prefix import (
        PREFIX_HIT_TOKENS_C,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.router import (
        _AFFINITY_C,
        LocalReplica,
        Router,
    )

    TOKENS_PER_S = 400.0  # per-replica decode rate (fake, shared window)
    MAX_ROWS = 8  # per-replica admission ceiling
    SHARE = 0.75  # the ISSUE's acceptance point
    PREFIX_POOL = 2  # two families over two replicas: affinity can split
    PREFIX_TOKENS = 192
    N = 96
    BUDGETS = (24, 48, 96)
    mean_tokens = sum(BUDGETS) / len(BUDGETS)
    capacity = TOKENS_PER_S * MAX_ROWS
    # offered decode demand ~0.8× ONE replica's ceiling → the 2-fleet
    # runs ~40% utilised: TTFT is join-prefill-dominated (the channel
    # affinity improves), not queue-saturation noise
    interarrival_s = mean_tokens / (capacity * 0.8)

    def fam_total(fam) -> float:
        return sum(c.value for c in fam._children.values())

    def run_arm(policy: str):
        workload = build_workload(
            N,
            interarrival_s,
            seed=19,
            model="bench:affinity",
            budgets=list(BUDGETS),
            stop_at_eos=False,
            shared_prefix_frac=SHARE,
            prefix_pool=PREFIX_POOL,
            shared_prefix_tokens=PREFIX_TOKENS,
        )
        prompt_tokens = sum(
            len(r.prompt.encode("utf-8")) + 1 for _, r in workload
        )
        replicas = [
            LocalReplica(
                f"r{i}",
                FakeBackend(
                    tokens_per_s=TOKENS_PER_S,
                    simulate_delay=True,
                    max_rows=MAX_ROWS,
                    prefix_share=True,
                    prefix_store_hbm_bytes=32 * 1024,
                    prefix_store_host_bytes=0,
                ),
            )
            for i in range(2)
        ]
        hit0 = fam_total(PREFIX_HIT_TOKENS_C)
        aff0 = fam_total(_AFFINITY_C)
        router = Router(replicas, policy=policy, probe_interval_s=0.25)
        router.start()
        try:
            records = run_load(router.dispatch, workload)
        finally:
            router.stop()
        hit_tokens = int(fam_total(PREFIX_HIT_TOKENS_C) - hit0)
        summary = summarize(records)
        return {
            "policy": policy,
            "requests": N,
            "shared_prefix_frac": SHARE,
            "agg_tokens_per_s": summary.get("agg_tokens_per_s"),
            "ttft_p50_s": summary.get("ttft_p50_s"),
            "ttft_p99_s": summary.get("ttft_p99_s"),
            "completion_p95_s": summary.get("completion_p95_s"),
            "errors": summary.get("errors"),
            "decode_tokens": sum(r.get("tokens") or 0 for r in records),
            "prompt_tokens": prompt_tokens,
            "prefix_hit_tokens": hit_tokens,
            "prefill_computed_tokens": prompt_tokens - hit_tokens,
            "affinity_hits": fam_total(_AFFINITY_C) - aff0,
            "per_replica": summary.get("replicas"),
        }

    arms = {
        "least_queue": run_arm("least-queue"),
        "affinity": run_arm("affinity"),
    }

    def ratio(key):
        va, vb = arms["affinity"].get(key), arms["least_queue"].get(key)
        return round(va / vb, 3) if va and vb else None

    line = {
        "metric": "affinity_routing",
        "unit": "ttft_p99_s",
        "replica_model": {
            "tokens_per_s": TOKENS_PER_S,
            "max_rows": MAX_ROWS,
            "prefix_store_hbm_bytes": 32 * 1024,
        },
        "arms": arms,
        "token_parity": (
            arms["affinity"]["decode_tokens"]
            == arms["least_queue"]["decode_tokens"]
            and not arms["affinity"]["errors"]
            and not arms["least_queue"]["errors"]
        ),
        "ttft_p99_affinity_vs_least_queue": ratio("ttft_p99_s"),
        "prefill_computed_affinity_vs_least_queue": ratio(
            "prefill_computed_tokens"
        ),
        "note": (
            "fake replicas are calibrated capacity models with "
            "budget-capped prefix stores; the figures measure the "
            "ROUTER's locality preservation (digest federation + "
            "probe-side estimation), not engine speed — on real engines "
            "each replica is one mesh/host behind serve-fleet "
            "--route-policy affinity"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    _sys.stdout.flush()
    return 0


def _tp_dp_continuous_arm(dp: int, tp: int) -> int:
    """ONE mesh-shape arm of the tp_dp_continuous A/B, in its own
    process (the parent pins ``xla_force_host_platform_device_count``
    to dp×tp). Builds a dp×tp mesh and, for EVERY cache layout
    (contiguous/paged × bf16/int8kv), runs the controlled
    fixed-occupancy slice-timing phase + bit-exact token parity vs the
    same engine's solo path. Prints ONE JSON line."""
    import os as _os
    import statistics as _stats

    import jax
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    n_dev = dp * tp
    if len(jax.devices()) < n_dev:
        print(json.dumps({"error": f"need {n_dev} devices, have {len(jax.devices())}"}))
        return 1
    cfg = dataclasses.replace(
        get_model_config("qwen2:1.5b").tiny(),
        n_heads=8, n_kv_heads=8, d_ff=128, d_model=64, d_head=16,
        max_seq_len=1024,
    )
    spec = MeshSpec.dp_tp(dp, tp) if dp > 1 else MeshSpec.tp_only(tp)
    mesh = build_mesh(spec, devices=jax.devices()[:n_dev])
    slice_steps = 8
    rows = int(_os.environ.get("BENCH_TPDP_ROWS", "8"))  # divides dp≤4
    budget = 48
    layouts = {}
    for name, paged, kv in (
        ("contiguous-bf16", False, None),
        ("contiguous-int8kv", False, "int8"),
        ("paged-bf16", True, None),
        ("paged-int8kv", True, "int8"),
    ):
        engine = TensorParallelEngine(
            mesh=mesh,
            registry={cfg.name: cfg},
            dtype=jnp.float32,
            paged_kv=paged,
            kv_quantize=kv,
        )
        fleet = [
            GenerationRequest(
                cfg.name, f"dp row {i} holds its slot",
                max_new_tokens=budget, stop_at_eos=False, seed=200 + i,
            )
            for i in range(rows)
        ]
        solo = [engine.generate(r) for r in fleet]  # warms every shape
        sess = engine.decode_open(
            fleet, reserve_rows=rows, slice_steps=slice_steps
        )
        dp_shards = sess.dp_shards
        sess.step(slice_steps)  # first slice pays any residual compile
        slice_walls, results = [], []
        while sess.active:
            full = sess.active == rows
            t0 = time.monotonic()
            retired = sess.step(slice_steps)
            if full and sess.active == rows:
                slice_walls.append(time.monotonic() - t0)
            results.extend(retired)
        parity = all(
            got.tokens == ref.tokens
            for ref, got in zip(
                solo,
                sorted(results, key=lambda r: fleet.index(r.request)),
            )
        )
        sess.close()
        mean_slice = _stats.mean(slice_walls) if slice_walls else None
        layouts[name] = {
            "dp_shards": dp_shards,
            "parity_vs_solo": parity,
            "full_occupancy_slices": len(slice_walls),
            "mean_step_s": (
                round(mean_slice / slice_steps, 6) if mean_slice else None
            ),
        }
    line = {
        "arm": "tp_dp_continuous",
        "dp": dp,
        "tp": tp,
        "devices": n_dev,
        "backend": jax.default_backend(),
        "model": cfg.name,
        "rows": rows,
        "slice_steps": slice_steps,
        "layouts": layouts,
    }
    print(json.dumps(line))
    return 0


def tp_dp_continuous_bench() -> int:
    """tp×dp in-mesh row sharding A/B (ISSUE 19): the stepped-decode
    controlled phase on forced-host 1×1 vs 2×2 vs 1×4 (tp×dp) meshes,
    one subprocess per mesh shape (a device count is process-lifetime),
    ALL FOUR cache layouts per arm with bit-exact token parity vs solo.

    The dp axis shards the ROW dimension of every batch-position carry
    leaf (and the page pool's page dim) under the same divisibility
    fallback as the heads rule, so the SAME scheduler loop serves a
    data-parallel×tensor-parallel mesh with no collective on the row
    axis. On the CPU dev environment the step ratios are SPMD-overhead
    figures (virtual devices share one CPU — expect ≤1×); the bench
    exists so the identical entry run on a real slice fills in the
    hardware column and so parity/dp-engagement regressions are visible
    per-layout in CI-adjacent runs. Prints ONE JSON line."""
    import os as _os
    import subprocess as _sp

    shapes = ((1, 1), (2, 2), (4, 1))  # (dp, tp): 1×1, 2×2 tp×dp, 1×4
    arms = {}
    for dp, tp in shapes:
        n_dev = dp * tp
        env = dict(_os.environ)
        env["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu") or "cpu"
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_dev}")
        env["XLA_FLAGS"] = " ".join(flags)
        proc = _sp.run(
            [sys.executable, _os.path.abspath(__file__),
             "_tp_dp_continuous_arm", str(dp), str(tp)],
            capture_output=True, text=True, env=env,
            cwd=_os.path.dirname(_os.path.abspath(__file__)),
            timeout=1800,
        )
        key = f"tp{tp}_dp{dp}"
        last = (proc.stdout.strip().splitlines() or ["{}"])[-1]
        try:
            arms[key] = json.loads(last)
        except json.JSONDecodeError:
            arms[key] = {
                "error": f"arm {key} emitted no JSON",
                "stdout_tail": proc.stdout[-500:],
                "stderr_tail": proc.stderr[-500:],
            }
        if proc.returncode != 0 and "error" not in arms[key]:
            arms[key]["error"] = f"exit {proc.returncode}"

    def step_s(key, layout="paged-bf16"):
        return ((arms.get(key, {}).get("layouts") or {}).get(layout) or {}).get(
            "mean_step_s"
        )

    base = step_s("tp1_dp1")
    ratios = {
        key: (
            round(base / step_s(key), 3)
            if base and step_s(key)
            else None
        )
        for key in ("tp2_dp2", "tp1_dp4")
    }
    parity_all = all(
        lay.get("parity_vs_solo") is True
        for arm in arms.values()
        for lay in (arm.get("layouts") or {}).values()
    ) and all("error" not in arm for arm in arms.values())
    dp_engaged = all(
        lay.get("dp_shards") == arm.get("dp")
        for key, arm in arms.items()
        if arm.get("dp", 1) > 1
        for lay in (arm.get("layouts") or {}).values()
    )
    line = {
        "metric": "tp_dp_continuous",
        "unit": "step_time_ratio",
        "arms": arms,
        "measured_step_ratio_1x1_to_2x2": ratios.get("tp2_dp2"),
        "measured_step_ratio_1x1_to_1x4": ratios.get("tp1_dp4"),
        "token_parity_all_layouts_all_meshes": parity_all,
        "dp_engaged_all_layouts": dp_engaged,
        "note": (
            "measured ratios are forced-host CPU SPMD overhead unless "
            "run on a real slice; dp shards the row dim (no collective "
            "on it), so on hardware the dp axis scales throughput at "
            "~flat step time while tp divides the per-step FLOPs"
        ),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def slo_overhead_bench() -> int:
    """Overhead micro-arm for ISSUE 17's windowed telemetry: the SAME
    tiny-CPU stepped-decode workload (real JaxEngine, continuous
    scheduler, seeded Poisson arrivals) run three ways —

    - ``telemetry``: obs on, no ring/SLO (the pre-ISSUE baseline);
    - ``slo``: obs on + a TimeSeriesRing sampler at 10 Hz (10x the
      shipped 1 s cadence — a deliberate worst case) + an SLOEngine
      evaluating two objectives every tick;
    - ``off``: kill switch on WITH the ring/SLO still configured — the
      sampler must refuse to start, restoring full parity.

    Budget: the ``slo`` arm's aggregate tokens/s within 2% of the
    ``telemetry`` arm's (recorded in docs/PERF.md). Each arm runs twice
    and keeps its best window (BATCH_STAT), like the decode bench.
    Prints ONE JSON line."""
    import os as _os
    import sys as _sys

    _sys.path.insert(
        0, _os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "scripts")
    )
    import jax
    import jax.numpy as jnp
    from poisson_load import build_workload, run_load, summarize

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu import obs
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import slo as obs_slo
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import (
        timeseries as obs_ts,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    cfg = get_model_config("qwen2:1.5b").tiny()
    engine = JaxEngine(registry={cfg.name: cfg}, dtype=jnp.float32)

    n = int(_os.environ.get("BENCH_SLO_REQUESTS", "16"))
    mean_ms = float(_os.environ.get("BENCH_SLO_INTERARRIVAL_MS", "30"))
    workload = build_workload(
        n, mean_ms / 1e3, seed=11, model=cfg.name, budgets=(8, 16, 32),
        prompts=("alpha beta", "gamma delta epsilon", "zeta eta"),
        stop_at_eos=False,  # fixed lengths: every arm does equal work
    )

    was_enabled = obs.enabled()
    interval_s = 0.1  # 10x the shipped cadence: overhead upper bound
    spec = "ttft_p99_ms<=250,completion_p95_s<=4"

    def run_arm(enable: bool, with_slo: bool) -> dict:
        (obs.enable if enable else obs.disable)()
        sampler = None
        if with_slo:
            ring = obs_ts.TimeSeriesRing(interval_s=interval_s)
            slo_engine = obs_slo.SLOEngine(
                obs_slo.parse_slo_spec(spec), ring, name="bench"
            )

            def _tick():
                ring.sample_once()
                slo_engine.evaluate()

            sampler = obs_ts.SamplerThread(
                _tick, interval_s=interval_s, name="bench-ts-sampler"
            )
            started = sampler.start()
            assert started is enable  # kill switch: never starts when off
        sched = ContinuousScheduler(engine)
        sched.start()
        try:
            records = run_load(sched.submit, workload)
        finally:
            sched.stop()
            if sampler is not None:
                sampler.stop()
        return summarize(records)

    arms = {}
    try:
        # warm-up: one full throwaway pass through the measured path so
        # every XLA shape (prefill buckets, stepped decode, admission
        # resizes) compiles BEFORE any arm is timed — arm order must
        # not decide the comparison
        run_arm(True, False)
        for name, enable, with_slo in (
            ("telemetry", True, False),
            ("slo", True, True),
            ("off", False, True),
        ):
            runs = [run_arm(enable, with_slo) for _ in range(BATCH_TIMED_RUNS)]
            arms[name] = max(
                runs, key=lambda s: s.get("agg_tokens_per_s") or 0.0
            )
    finally:
        (obs.enable if was_enabled else obs.disable)()

    def tps(name):
        return arms[name].get("agg_tokens_per_s") or 0.0

    overhead_pct = (
        round((tps("telemetry") - tps("slo")) / tps("telemetry") * 100.0, 2)
        if tps("telemetry")
        else None
    )
    line = {
        "metric": "slo_overhead",
        "unit": "tokens_per_s",
        "model": cfg.name,
        "backend": jax.default_backend(),
        "requests": n,
        "mean_interarrival_ms": mean_ms,
        "sampler_interval_s": interval_s,
        "slo_spec": spec,
        "timed_runs": BATCH_TIMED_RUNS,
        "stat": BATCH_STAT,
        "arms": arms,
        "slo_overhead_pct": overhead_pct,
        "overhead_budget_pct": 2.0,
        "kill_switch_tokens_per_s": tps("off"),
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def pd_disagg_bench() -> int:
    """Disaggregated prefill/decode A/B (ISSUE 18): in-flight
    inter-slice gap p99 + TTFT p99 of a 1-prefill + 1-decode role fleet
    vs 2 MIXED chunked replicas at matched hardware, on one seeded
    heavy-tailed lognormal trace (scripts/poisson_load.py).

    The mechanism under test: on a mixed replica every newcomer's
    chunked prefill runs inside the shared decode loop, so a
    heavy-tailed long prompt STALLS every in-flight stream for its
    chunk walls (the fake sleeps chunk/(tokens_per_s·8) per join_step —
    the same interference a real chunked-prefill slice has). The disagg
    fleet takes prefill on the prefill replica, ships the primed row
    (swap-policy bundle, zero re-prefill at seat) and decodes on the
    decode replica — in-flight streams never share a loop with prefill,
    which is THE inter-slice-gap tail claim of prefill/decode
    disaggregation. TTFT is client-observed at the decode side's first
    relayed chunk, so the transfer toll is IN the reported figure.

    Also records: a drain-latency column (evacuating a mid-stream row
    via live migration vs waiting the row out) and bit-exact token
    parity of a migrated row on all four real-engine cache layouts
    (contig/paged × bf16/int8-KV), with exact page free-count
    restoration on both pools. Prints ONE JSON line."""
    import os
    import threading

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from scripts.poisson_load import build_workload, percentile

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
        FakeBackend,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.router import (
        LocalReplica,
        Router,
    )

    TOKENS_PER_S = 400.0  # per-replica decode rate (fake, shared window)
    MAX_ROWS = 8  # per-replica admission ceiling (the HBM stand-in)
    BUDGETS = (48, 96, 160)
    N = 64
    MEAN_INTERARRIVAL_S = 0.08
    # heavy tail: median 64 prompt tokens, sigma 1.5 → the p99 draw
    # saturates the 2048 clamp; on a mixed replica each such prompt
    # stalls the shared decode loop ~chunk/(tokens_per_s·8) s per
    # 256-token chunk wall — 8 walls of ~80 ms for a clamped draw
    LOGNORM = dict(
        prompt_len_dist="lognormal",
        prompt_len_median=64.0,
        prompt_len_sigma=1.5,
        prompt_len_max=2048,
    )

    def trace():
        return build_workload(
            N,
            MEAN_INTERARRIVAL_S,
            seed=18,
            model="bench:pd",
            budgets=list(BUDGETS),
            stop_at_eos=False,
            **LOGNORM,
        )

    def fresh_backend():
        return FakeBackend(
            tokens_per_s=TOKENS_PER_S,
            simulate_delay=True,
            max_rows=MAX_ROWS,
        )

    def run_stream_load(router, workload):
        """Per-request client threads streaming through the router's
        front door, recording EVERY chunk arrival — TTFT at first
        chunk, inter-slice gaps between consecutive chunk walls while
        the row is in flight (run_load only keeps server-side TTFT;
        the gap tail is this bench's whole point)."""
        records = [None] * len(workload)
        start = time.monotonic()

        def client(i, offset, request):
            delay = start + offset - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t_submit = time.monotonic()
            rec = {"gaps": [], "tokens": 0}
            prev = None
            final = None
            try:
                for ch in router.dispatch_stream(request):
                    now = time.monotonic()
                    if ch.done:
                        final = ch.result
                        break
                    if not ch.tokens:
                        continue
                    if prev is None:
                        rec["ttft_s"] = now - t_submit
                    else:
                        rec["gaps"].append(now - prev)
                    prev = now
                    rec["tokens"] += len(ch.tokens)
            except BaseException as exc:  # noqa: BLE001
                rec["error"] = f"{type(exc).__name__}: {exc}"
            rec["completion_s"] = time.monotonic() - t_submit
            if final is not None and final.extras:
                sched = final.extras.get("sched") or {}
                route = final.extras.get("router") or {}
                if sched.get("migrated"):
                    rec["migrated"] = True
                if route.get("role"):
                    rec["role"] = route["role"]
            records[i] = rec

        threads = [
            threading.Thread(target=client, args=(i, off, req), daemon=True)
            for i, (off, req) in enumerate(workload)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [r for r in records if r is not None]

    def arm_summary(records):
        ok = [r for r in records if "error" not in r]
        gaps = [g for r in ok for g in r["gaps"]]
        ttfts = [r["ttft_s"] for r in ok if r.get("ttft_s") is not None]
        comps = [r["completion_s"] for r in ok]
        out = {
            "requests": len(records),
            "errors": len(records) - len(ok),
            "tokens": sum(r["tokens"] for r in ok),
            "migrated": sum(1 for r in ok if r.get("migrated")),
            "gap_samples": len(gaps),
            "gap_p50_ms": round(percentile(gaps, 50) * 1e3, 2),
            "gap_p95_ms": round(percentile(gaps, 95) * 1e3, 2),
            "gap_p99_ms": round(percentile(gaps, 99) * 1e3, 2),
            "completion_p95_s": round(percentile(comps, 95), 4),
        }
        if ttfts:
            out["ttft_p50_s"] = round(percentile(ttfts, 50), 4)
            out["ttft_p99_s"] = round(percentile(ttfts, 99), 4)
        roles = sorted({r["role"] for r in ok if r.get("role")})
        if len(roles) > 1 or (roles and roles != ["mixed"]):
            out["by_role"] = {
                name: sum(1 for r in ok if r.get("role") == name)
                for name in roles
            }
        return out

    def run_arm(replicas):
        router = Router(replicas, probe_interval_s=0.25)
        router.start()
        try:
            records = run_stream_load(router, trace())
        finally:
            router.stop()
        return arm_summary(records)

    arms = {
        "disagg_1p1d": run_arm(
            [
                LocalReplica("p", fresh_backend(), role="prefill"),
                LocalReplica("d", fresh_backend(), role="decode"),
            ]
        ),
        "mixed2": run_arm(
            [
                LocalReplica("m1", fresh_backend()),
                LocalReplica("m2", fresh_backend()),
            ]
        ),
    }

    # -- drain-latency column: evacuate a mid-stream row (live
    # migration to the survivor) vs wait it out ---------------------------
    def drain_arm(migrate: bool):
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (  # noqa: E501
            GenerationRequest,
        )

        router = Router(
            [
                LocalReplica("v", fresh_backend()),
                LocalReplica("s", fresh_backend()),
            ],
            probe_interval_s=0.25,
        )
        router.start()
        req = GenerationRequest(
            "bench:pd", "drain latency probe", max_new_tokens=600,
            stop_at_eos=False,
        )
        toks = []
        err = [None]

        def consume():
            try:
                for ch in router.dispatch_stream(req):
                    if not ch.done:
                        toks.extend(ch.tokens)
            except BaseException as exc:  # noqa: BLE001
                err[0] = exc

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 10.0
            while len(toks) < 10 and time.monotonic() < deadline:
                time.sleep(0.005)
            victim = next(
                r.name for r in router.replicas() if r.outstanding > 0
            )
            t0 = time.monotonic()
            drained = router.drain(victim, timeout_s=30.0, migrate=migrate)
            drain_s = time.monotonic() - t0
            t.join(timeout=30.0)
            return {
                "drained": bool(drained),
                "drain_s": round(drain_s, 4),
                "tokens_delivered": len(toks),
                "complete": len(toks) == 600 and err[0] is None,
            }
        finally:
            router.stop()

    drain = {
        "evacuate_migrate": drain_arm(True),
        "wait_out": drain_arm(False),
    }
    ev, wo = drain["evacuate_migrate"]["drain_s"], drain["wait_out"]["drain_s"]
    drain["evacuation_speedup"] = round(wo / ev, 2) if ev else None

    # -- bit-exact migrated-row parity on all four real cache layouts ------
    def parity_all_layouts():
        import jax.numpy as jnp

        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (  # noqa: E501
            GenerationRequest,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (  # noqa: E501
            JaxEngine,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (  # noqa: E501
            get_model_config,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.migrate import (  # noqa: E501
            export_bundle,
            import_bundle,
        )

        registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
        layouts = {
            "contig-bf16": (False, None),
            "contig-int8": (False, "int8"),
            "paged-bf16": (True, None),
            "paged-int8": (True, "int8"),
        }
        out = {}
        for name, (paged, kvq) in layouts.items():
            src = JaxEngine(
                registry=dict(registry), dtype=jnp.float32,
                paged_kv=paged, kv_quantize=kvq,
            )
            dst = JaxEngine(
                registry=dict(registry), dtype=jnp.float32,
                paged_kv=paged, kv_quantize=kvq,
            )
            anchor_s = GenerationRequest(
                "tiny", "source anchor", max_new_tokens=16,
                stop_at_eos=False,
            )
            anchor_d = GenerationRequest(
                "tiny", "destination anchor", max_new_tokens=16,
                stop_at_eos=False,
            )
            victim = GenerationRequest(
                "tiny", "the migrating row", max_new_tokens=16,
                stop_at_eos=False, seed=13,
            )
            solo = src.generate(victim).tokens
            s_sess = src.decode_open([anchor_s, victim], reserve_rows=4)
            d_sess = dst.decode_open([anchor_d], reserve_rows=4)
            s_idle = s_sess.pool.n_pages - 1 if paged else None
            d_idle = d_sess.pool.n_pages - 1 if paged else None
            s_sess.step(4)
            free_s = s_sess.pool.free_pages if paged else None
            pr = s_sess.preempt(victim, policy="swap")
            bundle = json.loads(
                json.dumps(export_bundle(pr, reason="disagg", streamed=0))
            )
            s_sess.resume_discard(pr)
            src_freed = (
                s_sess.pool.free_pages == free_s + pr.n_own_pages
                if paged
                else None
            )
            pr2 = import_bundle(bundle)
            pend = d_sess.resume_begin(pr2, 64)
            while not d_sess.join_step(pend):
                pass
            d_sess.join_commit(pend)
            results = {}
            for sess in (s_sess, d_sess):
                while sess.active:
                    for res in sess.step(8):
                        results[res.request.prompt] = res
            tokens_equal = results[victim.prompt].tokens == solo
            s_sess.close()
            d_sess.close()
            out[name] = {
                "tokens_equal": bool(tokens_equal),
                "src_pages_freed_exact": src_freed,
                "pools_restored_idle": (
                    (
                        s_sess.pool.free_pages == s_idle
                        and d_sess.pool.free_pages == d_idle
                    )
                    if paged
                    else None
                ),
            }
        return out

    parity = parity_all_layouts()

    d_gap = arms["disagg_1p1d"]["gap_p99_ms"]
    m_gap = arms["mixed2"]["gap_p99_ms"]
    line = {
        "metric": "pd_disagg_interslice_gap_p99_ms",
        "value": d_gap,
        "unit": "ms",
        # >1 = the disagg fleet's in-flight gap tail beats the mixed
        # fleet's at matched hardware (the acceptance bar)
        "vs_baseline": round(m_gap / d_gap, 3) if d_gap else None,
        "replica_model": {
            "tokens_per_s": TOKENS_PER_S,
            "max_rows": MAX_ROWS,
            "replicas_per_arm": 2,
        },
        "workload": {
            "n": N,
            "mean_interarrival_s": MEAN_INTERARRIVAL_S,
            "budgets": list(BUDGETS),
            **LOGNORM,
        },
        "arms": arms,
        "ttft_p99_disagg_vs_mixed": (
            round(
                arms["disagg_1p1d"]["ttft_p99_s"]
                / arms["mixed2"]["ttft_p99_s"],
                3,
            )
            if arms["mixed2"].get("ttft_p99_s")
            else None
        ),
        "drain": drain,
        "parity": parity,
    }
    _attach_obs(line)
    print(json.dumps(line))
    return 0


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "continuous_batching":
        return continuous_batching_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "router_fleet":
        return router_fleet_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "tp_continuous":
        return tp_continuous_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "_tp_continuous_arm":
        return _tp_continuous_arm(int(sys.argv[2]))
    if len(sys.argv) > 1 and sys.argv[1] == "affinity_routing":
        return affinity_routing_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "tp_dp_continuous":
        return tp_dp_continuous_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "_tp_dp_continuous_arm":
        return _tp_dp_continuous_arm(int(sys.argv[2]), int(sys.argv[3]))
    if len(sys.argv) > 1 and sys.argv[1] == "chunked_join":
        return chunked_join_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "streaming_cancellation":
        return streaming_cancellation_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "shared_prefix":
        return shared_prefix_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "radix_prefix":
        return radix_prefix_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "model_fleet":
        return model_fleet_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "preemption_overload":
        return preemption_overload_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "spec_continuous":
        return spec_continuous_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "spec_sampled":
        return spec_sampled_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "slo_overhead":
        return slo_overhead_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "pd_disagg":
        return pd_disagg_bench()
    if len(sys.argv) > 1 and sys.argv[1] == "tenant_attribution":
        return tenant_attribution_bench()
    import jax.numpy as jnp

    # The default entry measures the chip: a CPU run under the same
    # metric name would be read as a device number. The named sub-benches
    # above (parity and counts on CPU) take an argument.
    if not on_tpu():
        print(
            "bench: the default entry needs a TPU backend, found "
            f"{device_report()} - nothing was measured",
            file=sys.stderr,
        )
        return 2

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    cfg = get_model_config("qwen2:1.5b")
    quantize = "int8"
    engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16,
        decode_attention="auto",
        quantize=quantize,
    )

    prompt = "In 1000 words, please give me information about the solar system"
    warm = GenerationRequest(cfg.name, prompt, max_new_tokens=16)
    t0 = time.monotonic()
    engine.generate(warm)  # compile prefill + a decode bucket
    warm_s = time.monotonic() - t0

    request = GenerationRequest(cfg.name, prompt, max_new_tokens=256)
    result = engine.generate(request)  # compiles the 256 bucket
    result = engine.generate(
        dataclasses.replace(request, seed=1)
    )  # timed, warm

    tokens_per_s = result.generated_tokens / result.decode_s

    # Secondary figure: batched decode throughput (the serving story —
    # decode is bandwidth-bound, so rows share the weight stream; the
    # 128 rows balances the headline against bench wall time; override
    # with BENCH_BATCH_ROWS. Both batch engines are measured — the
    # contiguous cache AND the paged pool (round 5: with the
    # gather+fused-XLA parts and carry-resident side caches, the paged
    # engine WINS at wide batch — its side cache holds only generated
    # columns while the contiguous cache re-reads the full prompt+gen
    # shape every step; docs/PERF.md) — and the headline figure is the
    # better of the two, with both recorded.
    import os as _os

    batch_rows = int(_os.environ.get("BENCH_BATCH_ROWS", "128"))
    batch_tokens_per_s = None
    batch_by_engine = {}
    batch_windows = {}  # engine → the best run's (tokens, window_s)
    batch_reqs = [
        dataclasses.replace(request, seed=10 + i)
        for i in range(batch_rows)
    ]

    def measure_batch(name, eng):
        eng.generate_batch(batch_reqs)  # compile the batched loop
        # best of BATCH_TIMED_RUNS warm runs
        best = 0.0
        for _ in range(BATCH_TIMED_RUNS):
            batch_results = eng.generate_batch(batch_reqs)
            batch_tokens = sum(
                r.generated_tokens for r in batch_results
            )
            # Rows in one decode loop share one window (decode_s is
            # the batch wall-clock); a fleet past the memory-bounded
            # width runs as SEQUENTIAL sub-batches with their own
            # windows — sum the DISTINCT windows (identified by the
            # engine's explicit decode_window id, not by float
            # equality of decode_s) so the figure stays tokens over
            # real decode wall either way. A paged row's decode_s ends
            # with the slice that retired it: a window is its longest.
            windows = {}
            for r in batch_results:
                key = (r.extras or {}).get(
                    "decode_window", r.decode_s
                )
                windows[key] = max(windows.get(key, 0.0), r.decode_s)
            batch_decode_s = sum(windows.values())
            if batch_decode_s > 0 and batch_tokens / batch_decode_s > best:
                best = batch_tokens / batch_decode_s
                batch_windows[name] = (batch_tokens, batch_decode_s)
        batch_by_engine[name] = round(best, 2)

    measure_batch("contiguous", engine)
    # Free the contiguous engine's weights/caches BEFORE the paged
    # engine loads: two resident engines measured the paged loop at
    # ~half its solo throughput (HBM pressure), which would corrupt
    # the comparison.
    del engine
    paged_engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16,
        decode_attention="auto",
        quantize=quantize,
        paged_kv=True,
    )
    measure_batch("paged_kv", paged_engine)
    del paged_engine
    # The composed capacity mode (PR 1: int8 pages + budget-aware
    # admission): the BENCH trajectory tracks it from day one so a
    # step-speed or admission regression in the composition is
    # visible next to the modes it composes.
    paged_int8_engine = JaxEngine(
        registry={cfg.name: cfg},
        dtype=jnp.bfloat16,
        decode_attention="auto",
        quantize=quantize,
        paged_kv=True,
        kv_quantize="int8",
    )
    measure_batch("paged_int8", paged_int8_engine)
    del paged_int8_engine
    batch_tokens_per_s = max(batch_by_engine.values())

    # The study's energy model applied to this very run (per-engine
    # MXU/HBM/VPU power states, docs/PERF.md + profilers/tpu.py): the
    # bench line carries the modelled J/token and utilisation so the
    # recorded perf artifact and the energy story stay joined.
    energy_extra = {}
    import types as _types

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.experiments.llm_energy import (
        generation_stats_from,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.profilers.tpu import (
        TpuEnergyModelProfiler,
    )

    stats = generation_stats_from(cfg, result, quantize=quantize)
    ctx = _types.SimpleNamespace(scratch={"generation_stats": stats})
    cols = TpuEnergyModelProfiler().collect(ctx)
    if cols["joules_per_token"] is not None:
        energy_extra = {
            "joules_per_token_model": cols["joules_per_token"],
            "tpu_util_est": cols["tpu_util_est"],
            "tpu_power_model_W": cols["tpu_power_model_W"],
        }
    # Batched-serving J/token per measured engine, from each one's
    # best decode window: weights stream ONCE per step for the whole
    # batch (the amortisation batching exists for) while every row
    # streams its own KV — int8-KV halves the per-row KV term, which
    # is what the paged_int8 entry's model figure tracks.
    if batch_tokens_per_s is not None and batch_windows:
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
            decode_kv_stream_bytes,
            decode_vpu_unpack_ops_per_step,
            decode_weight_stream_bytes,
        )

        batch_energy = {}
        for name, (tokens, window_s) in batch_windows.items():
            gen_per_row = tokens / batch_rows
            per_row_total = result.prompt_tokens + gen_per_row
            mid_ctx = int(result.prompt_tokens + gen_per_row / 2)
            kv_mode = "int8" if name == "paged_int8" else None
            steps = gen_per_row
            bstats = {
                "flops": cfg.flops_per_token(int(per_row_total))
                * tokens,
                "bytes": (
                    decode_weight_stream_bytes(cfg, quantize)
                    + batch_rows
                    * decode_kv_stream_bytes(
                        cfg, mid_ctx, kv_quantize=kv_mode
                    )
                )
                * steps,
                "vpu_ops": decode_vpu_unpack_ops_per_step(
                    cfg, quantize
                )
                * steps,
                "duration_s": window_s,
                "generated_tokens": tokens,
            }
            bctx = _types.SimpleNamespace(
                scratch={"generation_stats": bstats}
            )
            bcols = TpuEnergyModelProfiler().collect(bctx)
            batch_energy[name] = {
                "joules_per_token_model": bcols["joules_per_token"],
                "tpu_power_model_W": bcols["tpu_power_model_W"],
            }
        energy_extra["batch_energy_model"] = batch_energy

    line = {
        "metric": "decode_tokens_per_s",
        "value": round(tokens_per_s, 2),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_s / BASELINE_TOKENS_PER_S, 3),
        "model": cfg.name,
        "device": device_report(),
        "quantize": quantize,
        "n_layers": cfg.n_layers,
        "generated_tokens": result.generated_tokens,
        "decode_s": round(result.decode_s, 3),
        "prefill_s": round(result.prefill_s, 4),
        "warmup_compile_s": round(warm_s, 1),
        "baseline_tokens_per_s": round(BASELINE_TOKENS_PER_S, 2),
        **energy_extra,
    }
    if batch_tokens_per_s is not None:
        # batch_rows + the timing discipline are recorded so cross-round
        # artifacts under the same key stay self-describing (ADVICE
        # round-4: r01-r03 ran 8 rows / 1 window, r04+ runs 128 rows /
        # best-of-2 — the numbers are not comparable without these)
        line.update(
            batch_rows=batch_rows,
            batch_timed_runs=BATCH_TIMED_RUNS,
            batch_stat=BATCH_STAT,
            # r05+: tokens / sum of DISTINCT decode windows, with fleets
            # ≤ the memory bound running as ONE window. r01–r04 divided
            # a 4-sub-batch fleet's tokens by its first 32-row window,
            # inflating the 128-row figure ~4× (docs/PERF.md round-5
            # correction) — r05+ batch numbers are honest and NOT
            # comparable to earlier rounds' under this key.
            batch_window_sum=True,
            batch_by_engine=batch_by_engine,
            batch_tokens_per_s=round(batch_tokens_per_s, 2),
            batch_vs_baseline=round(
                batch_tokens_per_s / BASELINE_TOKENS_PER_S, 3
            ),
        )
    # Obs attachments: the engines above recorded their prefill/decode
    # windows, step counts per attention path, pool occupancy and
    # modelled J/token into the shared registry — and their decisions
    # into the flight recorder — as they ran; attach both so
    # BENCH_*.json rows carry the distributions and the event counts,
    # not just the aggregate figures.
    _attach_obs(line)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
