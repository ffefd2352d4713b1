"""Serving-path observability: metrics, spans, and energy attribution.

The paper's contribution is *measurement* — Joules per fetched response —
but until this subsystem the serving path that ROADMAP's north star says
must carry heavy traffic was a black box. Three pieces, all stdlib-only
and default-on with a shared kill switch (env ``TPU_LLM_OBS=0`` or
``serve --no-telemetry``):

- :mod:`.metrics` — counters / gauges / fixed-bucket histograms with
  Prometheus text exposition (served at ``GET /metrics``) and a JSON
  snapshot (attached to bench lines). One process-wide ``REGISTRY``.
- :mod:`.trace` — monotonic-clock spans with parent links across the
  HTTP-handler → scheduler → engine thread hops, exported as Chrome
  trace events (``SpanTraceProfiler`` writes them per run next to
  ``jax_trace/``). One process-wide ``TRACER``.
- :mod:`.energy` — the ``profilers/tpu.py`` energy model (nominal + the
  documented coefficient box) folded into live per-request J and
  J/token estimates, surfaced in ``/metrics`` and in each result's
  ``extras["energy_model"]``.
- :mod:`.flight` — a bounded ring of schema'd structured events (the
  decisions the scheduler/engine actually made: admissions, join
  chunks, slice boundaries, retirements, fallbacks, pool exhaustion),
  served at ``GET /debug/flight`` with crash dumps on batch/session
  failure. One process-wide ``FLIGHT``.
- :mod:`.detect` — streaming anomaly detection (per-cell run CV against
  ROADMAP #1's <=5% target; passes of the serving loop that ran long
  against their rolling median, each with the cause the host's own
  account names) and goodput accounting for the stepped decode path.
- :mod:`.stall` — that account: one host sample a pass boundary (CPU
  time, run-queue time, throttling, faults, the collector's pause), the
  collector as a ``gc`` span, and the process's heartbeat, whose
  ``stall.process`` spans say whether a thread of ours held the
  interpreter lock or the machine did not run us.
- :mod:`.timeseries` — a fixed-capacity in-process ring of registry
  snapshots taken on a background cadence, serving WINDOWED rollups
  (counter rates/deltas, gauge min/mean/max, histogram quantiles from
  bucket deltas) at ``GET /debug/timeseries`` (ISSUE 17).
- :mod:`.slo` — SLO objectives (``serve --slo 'ttft_p99_ms<=250,...'``)
  evaluated over the ring: windowed attainment, multi-window burn-rate
  alerting (``slo_alert`` flight events, ``llm_slo_*`` families), fleet
  rollups at the router (ISSUE 17).

Instrumented layers: ``serve/server.py`` (HTTP timings, request root
spans, ``/metrics``), ``serve/scheduler.py`` (queue wait, window
collect, admission caps, batch composition), ``engine/jax_engine.py``
(prefill/decode windows, tokens/s, attention-path labels, energy
attribution), ``engine/paged_kv.py`` (pool occupancy / fragmentation).

Fleet-native since ISSUE 13: requests carry a wire trace context
(``x_trace`` → :class:`.trace.TraceContext`) every hop's spans and
flight events tag, :mod:`.metrics` parses and MERGES whole expositions
(``parse_exposition`` / ``merge_expositions`` — the router's
``llm_fleet_*`` federation), and :mod:`.energy` keeps the wasted-Joules
ledger (``llm_request_wasted_joules_total{cause=retry|recompute|swap}``)
that survives retries and preemption.
"""

from .flight import FLIGHT, FlightRecorder
from .metrics import (
    REGISTRY,
    MetricsRegistry,
    bucket_fraction_below,
    disable,
    enable,
    enabled,
    merge_expositions,
    parse_exposition,
    quantile_from_buckets,
)
from .slo import Objective, SLOEngine, parse_slo_spec
from .timeseries import SamplerThread, TimeSeriesRing
from .trace import TRACER, Span, SpanTracer, TraceContext, mint_trace_id

__all__ = [
    "REGISTRY",
    "MetricsRegistry",
    "TRACER",
    "Span",
    "SpanTracer",
    "TraceContext",
    "mint_trace_id",
    "FLIGHT",
    "FlightRecorder",
    "enabled",
    "enable",
    "disable",
    "merge_expositions",
    "parse_exposition",
    "quantile_from_buckets",
    "bucket_fraction_below",
    "TimeSeriesRing",
    "SamplerThread",
    "SLOEngine",
    "Objective",
    "parse_slo_spec",
]
