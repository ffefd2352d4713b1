"""CLI entry point: run a config file, or scaffold/describe one.

Reference: ``experiment-runner/__main__.py`` (config-file dispatch :52-79,
dynamic import :19-25, AST md5 :27-49) and
``ConfigValidator/CLIRegister/CLIRegister.py`` (command registry: config-create
/ prepare / help, :105-125). Usage::

    python -m cain_2025_device_remote_llm_energy_rep_pkg_tpu <config.py>
    python -m cain_2025_device_remote_llm_energy_rep_pkg_tpu config-create [dir]
    python -m cain_2025_device_remote_llm_energy_rep_pkg_tpu help
"""

from __future__ import annotations

import importlib.util
import inspect
import multiprocessing
import sys
import uuid
from pathlib import Path
from typing import List, Optional, Type

from . import term
from .config import ExperimentConfig
from .controller import ExperimentController
from .errors import CommandError, ConfigLoadError, ExperimentError

_TEMPLATE = '''"""Experiment config scaffold (edit every TODO)."""

from pathlib import Path

from cain_2025_device_remote_llm_energy_rep_pkg_tpu import (
    ExperimentConfig,
    Factor,
    RunTableModel,
)


class MyExperiment(ExperimentConfig):
    name = "new_runner_experiment"
    results_output_path = Path("experiments_output")
    time_between_runs_in_ms = 1000

    def create_run_table_model(self) -> RunTableModel:
        return RunTableModel(
            factors=[
                Factor("example_factor", ["treatment_a", "treatment_b"]),
            ],
            repetitions=1,
            data_columns=["example_metric"],
        )

    def start_run(self, context):
        pass  # TODO: start the measured activity

    def interact(self, context):
        pass  # TODO: wait for the activity to finish

    def populate_run_data(self, context):
        return {"example_metric": 0}  # TODO: report measurements
'''


def load_config_class(path: Path) -> Type[ExperimentConfig]:
    """Import a config module and find its ExperimentConfig subclass.

    The reference requires the class be named exactly ``RunnerConfig``
    (__main__.py:62-71); any single subclass is accepted here, with the name
    ``RunnerConfig`` preferred when several are defined.
    """
    spec = importlib.util.spec_from_file_location(f"_expconfig_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise ConfigLoadError(f"cannot import config file: {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    candidates: List[Type[ExperimentConfig]] = [
        obj
        for _, obj in inspect.getmembers(module, inspect.isclass)
        if issubclass(obj, ExperimentConfig)
        and obj is not ExperimentConfig
        and obj.__module__ == module.__name__
    ]
    if not candidates:
        raise ConfigLoadError(f"no ExperimentConfig subclass found in {path}")
    if len(candidates) > 1:
        named = [c for c in candidates if c.__name__ == "RunnerConfig"]
        if len(named) == 1:
            return named[0]
        raise ConfigLoadError(
            f"multiple ExperimentConfig subclasses in {path}: "
            f"{[c.__name__ for c in candidates]}; name one 'RunnerConfig'"
        )
    return candidates[0]


def run_config_file(path: Path) -> None:
    if not path.exists():
        raise CommandError(f"config file does not exist: {path}")
    # Children must inherit the wired event bus and config state.
    if multiprocessing.get_start_method(allow_none=True) != "fork":
        multiprocessing.set_start_method("fork", force=True)
    cls = load_config_class(path)
    config = cls()
    controller = ExperimentController(config, config_source=path.read_text())
    controller.do_experiment()


def config_create(target_dir: Optional[Path]) -> Path:
    """Scaffold a fresh config file (reference CLIRegister.py:14-61)."""
    target = target_dir or Path("examples")
    target.mkdir(parents=True, exist_ok=True)
    out = target / f"RunnerConfig-{uuid.uuid1()}.py"
    out.write_text(_TEMPLATE)
    term.log_ok(f"created config scaffold: {out}")
    return out


HELP = """usage: python -m cain_2025_device_remote_llm_energy_rep_pkg_tpu <command|config.py>

commands:
  <config.py>          run the experiment defined by the config file
  config-create [dir]  scaffold a new config file (default dir: examples/)
  analyze <exp_dir> [--filter-scope cell|subset|pooled]
                       (re)run the statistics pipeline over an experiment's
                       run_table.csv, writing analysis_report.{json,md} + plots;
                       --filter-scope picks the IQR strata (default `cell` =
                       model×location×length; `subset` = location×length, the
                       reference notebook's exact order, nb cells 11-13)
  recompute-energy <exp_dir> [--chips loc=n,...] [--quantize m=q,...]
                   [--trust-remote-timings]
                       recompute the modelled energy columns from the table's
                       persisted raw measurements (timings + token counts)
                       under the current energy model, then re-analyze;
                       --chips is the fallback topology and --quantize the
                       fallback per-model serving modes (model=mode with a
                       `default=` entry, the serve CLI's spec shape) for
                       tables predating the per-row `chips`/`quantize`
                       columns; --trust-remote-timings keeps such tables'
                       multi-chip remote windows as measured (disables the
                       rows-were-aliased assumption)
  prepare              validate the environment (JAX devices, RAPL access)
  serve [opts]         start the HTTP generation server (the framework-native
                       Ollama-equivalent): --host H --port N (default 11434),
                       --backend jax|jax-tp|fake, --tp N, --dp M,
                       --models a,b,c
                       (--backend jax-tp --tp N serves from an N-device
                       tensor-parallel mesh; adding --dp M grows a dp
                       axis that shards stepped sessions' ROW dim — KV
                       payload, page pool and row control split over dp
                       shards, so a tp×dp mesh serves dp× the rows of a
                       tp-only mesh — and composes with
                       --scheduler continuous: stepped decode sessions
                       carry an explicitly-sharded SPMD pytree — KV
                       pool/caches sharded over heads when they divide
                       the mesh, row state replicated — so joins,
                       retirements, cancellation and shared-prefix CoW
                       paging run unchanged on the mesh; on a dev box
                       XLA_FLAGS=--xla_force_host_platform_device_count=N
                       exercises the same path on virtual CPU devices),
                       --scheduler window|continuous --window-ms W
                       --max-batch B (request batching of concurrent
                       requests; off by default — --scheduler or
                       --window-ms turns it on. --scheduler defaults to
                       continuous
                       for real batched backends: iteration-level
                       admit/step/retire where rows retire and joiners
                       admit at decode-step granularity; window = classic
                       admission-window batches run to completion, with
                       --window-ms the collect window, default 50.
                       --batch-window-ms is the deprecated alias of
                       --window-ms; --no-budget-admission pins the cap
                       at --max-batch instead of raising it to the
                       engine's KV-budget estimate;
                       --decode-slice-steps N sets the continuous
                       scheduler's bounded decode-slice width (default
                       16, env DECODE_SLICE_STEPS) and
                       --prefill-chunk-tokens N the token budget of one
                       chunk of a mid-flight joiner's prefill (default
                       auto/256, env PREFILL_CHUNK_TOKENS) — together
                       they bound in-flight rows' stall per scheduler
                       iteration;
                       --ttft-slo-ms N rejects queued requests whose
                       wait alone already exceeds the TTFT SLO (HTTP
                       504, before any prefill is paid; off by
                       default);
                       SLO tiers + preemption: requests carry
                       x_priority (low|normal|high or any integer,
                       higher = more important; --default-priority T
                       stamps bare requests, default normal), the
                       scheduler queue is per-tier FIFO, and under
                       --scheduler continuous a higher-tier ticket
                       that cannot be admitted PREEMPTS the youngest
                       strictly-lower-tier in-flight row:
                       --preempt-policy swap (default) spills the
                       victim's KV pages to host memory and restores
                       them bit-exactly at resume, recompute drops
                       the KV and re-prefills prompt+generated through
                       the chunked-join machinery, off disables
                       preemption (shed-at-the-edge only);
                       --preempt-max-wait-s S ages a parked victim up
                       one tier per S seconds waited (starvation
                       protection, default 30).
                       Streaming: "stream": true serves SSE
                       through the continuous scheduler's per-slice
                       egress — a client hanging up retires its row
                       mid-flight and recycles its KV pages; requests
                       may carry x_deadline_ms, enforced pre-admission
                       AND mid-flight),
                       --hf model=/ckpt/dir (serve trained weights + that
                       checkpoint's tokenizer; repeatable),
                       --quantize int8|int4|none or per-model
                       "m1=int8,m2=int4,default=int8" (int8 for speed, int4
                       for HBM fit), --kv-quantize int8 (halve the decode
                       KV stream), --speculative target=draft[:k] or the
                       draft-only form --speculative draft[:k] (one draft
                       for every served target): eligible requests decode
                       via draft-verify — solo AND batched: continuous
                       sessions run per-row draft-verify rounds where
                       rows advance by their accepted-prefix length.
                       Greedy rows verify exactly; SAMPLED rows (0 <
                       temperature <= --spec-temperature-max, default 2)
                       use rejection resampling, provably matching plain
                       sampling's marginals. `draft` is a model name,
                       `ngram` (prompt-lookup drafting, zero extra
                       weights) or `cross:<model>` (draft on another
                       serving lane's resident model; fully-rejected
                       rounds bill draft Joules to the wasted-energy
                       ledger). Composes with joins, streaming
                       cancellation, shared-prefix CoW, --kv-quantize
                       int8 (the target cache is int8, the tiny draft
                       cache stays bf16) and --backend jax-tp;
                       --spec-accept-floor F makes a session whose
                       rolling measured acceptance drops below F fall
                       back to plain decode (llm_spec_fallback_total
                       {source}; per-source strikes park a losing
                       source until it re-arms; default: never),
                       --spec-draft-temperature T drafts sampled rows'
                       proposals at temperature T instead of each row's
                       own (a flatter q raises acceptance on sharp
                       rows; the accept math follows the proposal
                       distribution, so output marginals are provably
                       unchanged — default: draft at the row's
                       temperature),
                       --prefix-cache N (prompt-prefix KV
                       LRU), --paged-kv (batched decode over a paged KV
                       pool: mixed-length batches stop paying the widest
                       row's padding),
                       --prefix-share (persistent cross-session prefix
                       store: the ENGINE owns a radix tree over
                       refcounted pages — continuous-session joiners
                       whose prompt shares a published prefix map its
                       read-only pool pages and chunk-prefill only the
                       divergent tail, INCLUDING joiners in a later
                       session or after a scheduler restart; cold
                       prefix pages spill to host RAM and restore on
                       hit; works with --paged-kv and --kv-quantize
                       int8, seed-only reuse on contiguous caches) with
                       --prefix-index-entries N the per-model node
                       capacity (default 16, LRU),
                       --prefix-store-hbm-bytes B the store's device
                       budget (over-budget spills cold prefix pages to
                       host) and --prefix-store-host-bytes B its host
                       budget (over-budget evicts cold leaves),
                       --access-log (structured per-request log line:
                       method/path/status/duration; default off),
                       --no-telemetry (kill switch for /metrics, the
                       /debug/state + /debug/flight + /debug/timeseries
                       introspection endpoints, spans, the flight
                       recorder, the time-series sampler and
                       per-request energy attribution — default on;
                       env twin: TPU_LLM_OBS=0),
                       --slo 'ttft_p99_ms<=250,completion_p95_s<=4,
                       joules_per_token<=0.35' (SLO objectives over the
                       in-process time-series ring: ttft|completion|
                       queue_wait_pNN_{ms,s} target the NNth percentile
                       of the matching latency histogram,
                       joules_per_token the energy contract at a 0.95
                       default target; each objective's windowed
                       attainment + multi-window burn-rate alerts
                       publish as llm_slo_* families and slo_alert
                       flight events, windowed rollups serve on GET
                       /debug/timeseries?family=&window=&step=; ring
                       cadence/depth via env TPU_LLM_TS_INTERVAL_S /
                       TPU_LLM_TS_CAPACITY);
                       Replica fleets: --replicas N runs N fully
                       INDEPENDENT backend+scheduler replicas in this
                       process behind the front-door router
                       (serve/router.py — same wire protocol incl. SSE
                       streaming, x_priority, x_deadline_ms);
                       --route-policy least-queue|least-pages|
                       least-joules|round-robin picks the dispatch
                       policy (default least-queue) fed by per-replica
                       /healthz + /metrics probes every
                       --probe-interval-ms (default 1000); a ticket
                       whose replica refuses admission or dies before
                       its first streamed token retries ONCE on a
                       different replica (both attempts share ONE
                       x_trace id; the dead attempt's burned prefill
                       is charged to llm_request_wasted_joules_total
                       {cause="retry"} and rides x_extras.energy).
                       Fleet observability: requests may carry
                       x_trace {"id": hex, "parent": span} (minted at
                       the front door when absent) — every hop's spans
                       and flight events carry the trace id, GET
                       /debug/flight takes ?trace= and the router's
                       GET /debug/timeline?trace= reassembles one
                       request's cross-process lifecycle; the router's
                       GET /metrics additionally exposes llm_fleet_*
                       rollups (counters summed, histograms merged
                       bucket-wise, gauges re-labelled {replica=...})
                       federated from the replicas' scrapes.
                       Disaggregated prefill/decode: --role
                       mixed|prefill|decode stamps this server's role
                       (reported on /healthz; default mixed = classic
                       behavior). A PREFILL replica runs chunked-join
                       prefill to completion, exports the primed row
                       (KV pages as swap blobs + control state) and the
                       router ships it over POST /api/migrate to a
                       DECODE replica, which seats it via the resume
                       path and streams — one uninterrupted SSE stream,
                       TTFT stamped at the decode side's first chunk;
                       decode replicas never take fresh dispatch. The
                       transfer is charged to the wasted-energy ledger
                       (cause="migration", 2x bundle bytes) and counted
                       by llm_migrate_rows_total{reason}/llm_migrate_
                       bytes_total{direction}; a receiver failing
                       mid-transfer falls back to local decode on the
                       prefill replica (llm_router_retries_total
                       {reason="migrate_failed"}), never a dropped
                       ticket. --roles prefill,decode assigns
                       per-replica roles under --replicas N (cycling);
                       POST /admin/drain?replica=R&migrate=1 on the
                       router evacuates a replica's in-flight rows to
                       survivors before detach (wait-out when
                       migrate=0), POST /admin/add_replica?target=H:P
                       attaches a new one.
                       Multi-model serving: --model-policy small-first|
                       cheapest-joules hosts one continuous lane per
                       --models entry over ONE engine (decode slices of
                       different models interleave — no cross-model
                       head-of-line blocking; the KV envelope splits
                       across lanes; evicting a model with live rows is
                       deferred) and resolves model:"auto" requests by
                       the policy: cheapest-joules routes to the lowest
                       live J/token, small-first runs the smallest
                       model and ESCALATES to the biggest when the
                       answer is length-cut after at least
                       --escalate-max-tokens tokens (default 32; the
                       abandoned tokens charge llm_request_wasted_
                       joules_total{cause="escalation"}); the fleet's
                       merged loaded-models view serves on /api/ps and
                       the router's dispatch prefers replicas holding a
                       request's model warm
                       Tenant accounting: requests may carry x_tenant
                       (default "default"); terminal outcomes land in
                       llm_tenant_* (bounded table, overflow folds to
                       tenant="_other") and GET /debug/tenants serves
                       per-tenant aggregates (the router's merges the
                       fleet). --usage-ledger-dir DIR additionally
                       appends one JSONL record per terminal request
                       (monotonic seq, resumed across restarts) with a
                       periodic snapshot — the billing artifact
  serve-fleet --targets host:port[,host:port...] [--route-policy P]
                       [--port N] [--models a,b] [--probe-interval-ms M]
                       [--slo 'ttft_p99_ms<=250,...'] (fleet-wide SLOs:
                       the router's ring samples the federated
                       llm_fleet_* merge, so attainment and burn-rate
                       alerts are computed fleet-wide; per-replica
                       attainment rides /debug/state)
                       the front-door router over ALREADY-RUNNING
                       `serve` processes (one per host/chip) — the
                       multi-host twin of `serve --replicas N`; probes
                       each target's /healthz + /metrics and dispatches
                       by the same policies, federates their /metrics
                       into llm_fleet_* rollups, and serves the
                       cross-process /debug/timeline
  help                 show this message
"""


def serve_command(args: List[str]) -> None:
    """Run the generation server — the "remote" machine's side of the study
    (reference: a separately-installed Ollama server on the remote host,
    README.md:29-31; here it is part of the framework)."""
    port = None
    host = "0.0.0.0"
    backend_kind = "jax"
    tp = -1
    dp = 1  # >1 with --backend jax-tp: tp×dp mesh, rows sharded over dp
    models: Optional[List[str]] = None
    batch_window_ms = 0.0
    scheduler = None  # auto: continuous for real batched backends
    max_batch = None  # backend-aware default (serve/scheduler.py)
    budget_aware = None  # auto: KV-budget admission when estimable
    slice_steps = None  # continuous: engine DECODE_SLICE_STEPS default
    prefill_chunk_tokens = None  # continuous: engine auto default
    ttft_slo_ms = None  # no TTFT SLO: late requests serve late
    default_priority = None  # tier for requests without x_priority
    preempt_policy = None  # scheduler default ("swap")
    preempt_max_wait_s = None  # scheduler default (30 s aging clock)
    hf_checkpoints = {}
    quantize = None
    kv_quantize = None
    paged_kv = False
    speculative = {}
    spec_accept_floor = None  # speculative auto-fallback threshold
    spec_temperature_max = None  # sampled-spec eligibility cap (ISSUE 16)
    spec_draft_temperature = None  # independent draft-q flatten (ISSUE 18)
    prefix_cache = 0
    prefix_share = False
    prefix_index_entries = None
    prefix_store_hbm_bytes = None  # engine prefix-store HBM byte budget
    prefix_store_host_bytes = None  # engine prefix-store host byte budget
    access_log = False
    replicas = 1  # >1: a replica fleet behind the front-door router
    route_policy = None  # router default ("least-queue")
    probe_interval_ms = None  # router default (1000 ms)
    model_policy = None  # multi-model fleet: small-first|cheapest-joules
    escalate_max_tokens = None  # small-first cascade length-cut floor
    slo = None  # SLO objectives spec (ISSUE 17)
    role = None  # disagg serving role: mixed|prefill|decode (ISSUE 18)
    roles = None  # per-replica roles for --replicas N fleets
    usage_ledger_dir = None  # tenant usage ledger directory (ISSUE 20)
    it = iter(args)
    for arg in it:
        if arg == "--port":
            port = int(next(it, "11434"))
        elif arg == "--host":
            host = next(it, "0.0.0.0")
        elif arg == "--backend":
            backend_kind = next(it, "jax")
        elif arg == "--tp":
            tp = int(next(it, "-1"))
        elif arg == "--dp":
            dp = int(next(it, "1"))
            if dp < 1:
                raise CommandError("serve: --dp expects a positive integer")
        elif arg == "--models":
            models = [m for m in next(it, "").split(",") if m]
        elif arg in ("--window-ms", "--batch-window-ms"):
            # --batch-window-ms is the pre-continuous-scheduler spelling,
            # kept as an alias
            batch_window_ms = float(next(it, "0"))
        elif arg == "--scheduler":
            scheduler = next(it, "")
            if scheduler not in ("window", "continuous"):
                raise CommandError(
                    "serve: --scheduler expects 'window' or 'continuous'"
                )
        elif arg == "--max-batch":
            max_batch = int(next(it, "0")) or None
        elif arg == "--no-budget-admission":
            budget_aware = False
        elif arg == "--decode-slice-steps":
            slice_steps = int(next(it, "0")) or None
            if slice_steps is not None and slice_steps < 1:
                raise CommandError(
                    "serve: --decode-slice-steps expects a positive integer"
                )
        elif arg == "--prefill-chunk-tokens":
            prefill_chunk_tokens = int(next(it, "0")) or None
            if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
                raise CommandError(
                    "serve: --prefill-chunk-tokens expects a positive integer"
                )
        elif arg == "--ttft-slo-ms":
            ttft_slo_ms = float(next(it, "0")) or None
            if ttft_slo_ms is not None and ttft_slo_ms <= 0:
                raise CommandError(
                    "serve: --ttft-slo-ms expects a positive number"
                )
        elif arg == "--default-priority":
            from ..serve.protocol import parse_priority

            try:
                default_priority = parse_priority(next(it, ""))
            except ValueError as exc:
                raise CommandError(f"serve: --default-priority: {exc}")
        elif arg == "--preempt-policy":
            preempt_policy = next(it, "")
            if preempt_policy not in ("off", "swap", "recompute"):
                raise CommandError(
                    "serve: --preempt-policy expects 'off', 'swap' or "
                    "'recompute'"
                )
        elif arg == "--preempt-max-wait-s":
            try:
                preempt_max_wait_s = float(next(it, ""))
            except ValueError:
                raise CommandError(
                    "serve: --preempt-max-wait-s expects a number of "
                    "seconds (0 disables starvation aging)"
                )
            if preempt_max_wait_s < 0:
                raise CommandError(
                    "serve: --preempt-max-wait-s expects a number >= 0"
                )
        elif arg == "--hf":
            # --hf model=/path/to/checkpoint (repeatable): serve the model
            # from a local HF checkpoint (trained weights + its tokenizer)
            # instead of random-init — the analogue of `ollama pull`.
            spec = next(it, "")
            if "=" not in spec:
                raise CommandError("serve: --hf expects model=/path/to/dir")
            name, _, path = spec.partition("=")
            hf_checkpoints[name] = path
        elif arg == "--quantize":
            # "int8" | "int4" | "none" for every model, or a per-model
            # spec "qwen2:1.5b=int8,phi3:3.8b=int4,default=int8" (model
            # names may contain colons; '=' separates name from mode).
            spec = next(it, "int8")
            if "=" in spec:
                quantize = {}
                for entry in spec.split(","):
                    name, _, mode = entry.partition("=")
                    if not name or not mode:
                        raise CommandError(
                            "serve: --quantize per-model spec is "
                            "model=mode[,model=mode...]"
                        )
                    quantize[name] = None if mode == "none" else mode
            else:
                quantize = None if spec == "none" else spec
        elif arg == "--speculative":
            # --speculative target=draft[:k] (repeatable): eligible
            # requests for `target` decode via draft-and-verify with k
            # proposals (greedy verifies exactly; sampled rows use
            # rejection resampling — ISSUE 16). The DRAFT-ONLY form
            # `--speculative draft[:k]` (no '=') applies one draft to
            # EVERY served target (stored under the "default" key; a
            # model never self-drafts through it). Besides a model
            # name, `draft` may be `ngram` (prompt-lookup drafting,
            # zero extra weights) or `cross:<model>` (draft on another
            # lane's resident model). Model names may contain colons
            # (qwen2:1.5b), so only a trailing :<int> is treated as k.
            spec = next(it, "")
            if not spec:
                raise CommandError(
                    "serve: --speculative expects target=draft[:k] or "
                    "draft[:k] (draft: model name, ngram, cross:<model>)"
                )
            name, eq, rest = spec.partition("=")
            if not eq:
                name, rest = "default", spec
            head, _, tail = rest.rpartition(":")
            if head and tail.isdigit():
                draft, k = head, int(tail)
            else:
                draft, k = rest, 4
            if not name or not draft or k < 1:
                raise CommandError(
                    "serve: --speculative expects target=draft[:k] (or "
                    "draft[:k]) with k >= 1"
                )
            speculative[name] = (draft, k)
        elif arg == "--spec-accept-floor":
            # auto-fallback threshold: a speculating continuous session
            # whose rolling measured acceptance drops below this
            # fraction falls back to plain decode (0 disables).
            try:
                spec_accept_floor = float(next(it, ""))
            except ValueError:
                raise CommandError(
                    "serve: --spec-accept-floor expects a fraction in [0, 1)"
                )
            if not 0.0 <= spec_accept_floor < 1.0:
                raise CommandError(
                    "serve: --spec-accept-floor expects a fraction in [0, 1)"
                )
        elif arg == "--spec-temperature-max":
            # sampled-spec eligibility cap: requests with temperature in
            # (0, T] speculate via rejection resampling; hotter requests
            # serve plain. 0 restores the greedy-only gate.
            try:
                spec_temperature_max = float(next(it, ""))
            except ValueError:
                raise CommandError(
                    "serve: --spec-temperature-max expects a float >= 0"
                )
            if spec_temperature_max < 0.0:
                raise CommandError(
                    "serve: --spec-temperature-max expects a float >= 0"
                )
        elif arg == "--spec-draft-temperature":
            # independent draft proposal temperature: sampled rows'
            # draft sources propose at this flatter/sharper temperature
            # instead of the row's own sampler temperature; acceptance
            # math stays exact (q follows the proposals), so marginals
            # are unchanged — a pure acceptance-rate tuning knob.
            try:
                spec_draft_temperature = float(next(it, ""))
            except ValueError:
                raise CommandError(
                    "serve: --spec-draft-temperature expects a float > 0"
                )
            if spec_draft_temperature <= 0.0:
                raise CommandError(
                    "serve: --spec-draft-temperature expects a float > 0"
                )
        elif arg == "--prefix-cache":
            prefix_cache = int(next(it, "4"))
        elif arg == "--prefix-share":
            prefix_share = True
        elif arg == "--prefix-index-entries":
            prefix_index_entries = int(next(it, "16"))
            if prefix_index_entries < 1:
                raise CommandError(
                    "serve: --prefix-index-entries expects a positive integer"
                )
        elif arg == "--prefix-store-hbm-bytes":
            # device-byte budget of the ISSUE-14 engine prefix store:
            # over-budget spills LRU-cold prefix pages to host RAM
            prefix_store_hbm_bytes = int(next(it, "0"))
            if prefix_store_hbm_bytes < 0:
                raise CommandError(
                    "serve: --prefix-store-hbm-bytes expects bytes >= 0"
                )
        elif arg == "--prefix-store-host-bytes":
            # host-byte budget (spilled blobs + seed slabs): over-budget
            # evicts LRU-cold prefix-store leaves outright
            prefix_store_host_bytes = int(next(it, "0"))
            if prefix_store_host_bytes < 0:
                raise CommandError(
                    "serve: --prefix-store-host-bytes expects bytes >= 0"
                )
        elif arg == "--kv-quantize":
            kv_quantize = next(it, "int8")
            if kv_quantize == "none":
                kv_quantize = None
        elif arg == "--paged-kv":
            paged_kv = True
        elif arg == "--replicas":
            # N independent backend+scheduler replicas behind the
            # front-door router (serve/router.py); 1 = the classic
            # single-backend server.
            replicas = int(next(it, "1"))
            if replicas < 1:
                raise CommandError(
                    "serve: --replicas expects a positive integer"
                )
        elif arg == "--route-policy":
            from ..serve.router import ROUTE_POLICIES

            route_policy = next(it, "")
            if route_policy not in ROUTE_POLICIES:
                raise CommandError(
                    "serve: --route-policy expects one of "
                    + "|".join(ROUTE_POLICIES)
                )
        elif arg == "--probe-interval-ms":
            probe_interval_ms = float(next(it, "0")) or None
            if probe_interval_ms is not None and probe_interval_ms <= 0:
                raise CommandError(
                    "serve: --probe-interval-ms expects a positive number"
                )
        elif arg == "--model-policy":
            # Multi-model serving (ISSUE 15): host one continuous lane
            # per --models entry over ONE engine (shared HBM envelope)
            # and resolve model:"auto" by this policy.
            from ..serve.model_fleet import MODEL_POLICIES

            model_policy = next(it, "")
            if model_policy not in MODEL_POLICIES:
                raise CommandError(
                    "serve: --model-policy expects one of "
                    + "|".join(MODEL_POLICIES)
                )
        elif arg == "--escalate-max-tokens":
            # small-first cascade: a budget-cut answer escalates to the
            # big model only after at least this many tokens
            try:
                escalate_max_tokens = int(next(it, ""))
            except ValueError:
                raise CommandError(
                    "serve: --escalate-max-tokens expects a positive "
                    "integer"
                )
            if escalate_max_tokens < 1:
                raise CommandError(
                    "serve: --escalate-max-tokens expects a positive "
                    "integer"
                )
        elif arg == "--slo":
            # SLO objectives (ISSUE 17): attainment + multi-window
            # burn-rate alerting over the in-process time-series ring.
            from ..obs.slo import parse_slo_spec

            slo = next(it, "")
            try:
                parse_slo_spec(slo)  # validate at the CLI edge
            except ValueError as exc:
                raise CommandError(f"serve: --slo: {exc}")
        elif arg == "--role":
            # Disaggregated prefill/decode serving (ISSUE 18): a
            # prefill replica primes long-prompt rows and ships them
            # via /api/migrate; a decode replica seats migrated rows
            # but never takes fresh dispatch; mixed = today-behavior.
            from ..serve.protocol import SERVER_ROLES

            role = next(it, "")
            if role not in SERVER_ROLES:
                raise CommandError(
                    "serve: --role expects one of " + "|".join(SERVER_ROLES)
                )
        elif arg == "--roles":
            # Per-replica roles for --replicas N (e.g. --replicas 2
            # --roles prefill,decode); cycles if shorter than N.
            from ..serve.protocol import SERVER_ROLES

            roles = [r for r in next(it, "").split(",") if r]
            bad = [r for r in roles if r not in SERVER_ROLES]
            if not roles or bad:
                raise CommandError(
                    "serve: --roles expects a comma list drawn from "
                    + "|".join(SERVER_ROLES)
                )
        elif arg == "--usage-ledger-dir":
            # Tenant usage ledger (ISSUE 20): append-only JSONL of
            # terminal request outcomes under this directory, with a
            # periodic aggregate snapshot and seq resumption across
            # restarts (billing replays never double-bill).
            usage_ledger_dir = next(it, "")
            if not usage_ledger_dir:
                raise CommandError(
                    "serve: --usage-ledger-dir expects a directory path"
                )
        elif arg == "--access-log":
            access_log = True
        elif arg == "--no-telemetry":
            from ..obs import disable as obs_disable

            obs_disable()
        else:
            raise CommandError(f"serve: unrecognised option {arg!r}")

    from ..serve.protocol import DEFAULT_PORT
    from ..serve.server import GenerationServer

    if backend_kind != "fake":
        # The serving process pays all jit compiles — persist them.
        from ..utils.compile_cache import enable_compilation_cache
        from ..utils.device import device_report

        cache_dir = enable_compilation_cache()
        dev = device_report()
        term.log(
            f"serve: device platform={dev['platform']} "
            f"kind={dev['kind']!r} count={dev['count']}; "
            f"compile cache {cache_dir}"
        )
    def build_backend():
        """One fresh backend instance — called once for the classic
        single-backend server, N times for ``--replicas N`` (each
        replica owns a fully independent engine + KV budget)."""
        if backend_kind == "fake":
            import os

            from ..engine.fake import FakeBackend

            # --speculative on the fake backend runs the synthetic spec
            # protocol (k + draft source from the first configured
            # entry; acceptance via env FAKE_SPEC_ACCEPTANCE, default
            # 1.0) so the serving surface is demo-able with no
            # accelerator
            spec_k = (
                next(iter(speculative.values()))[1] if speculative else 0
            )
            spec_draft = (
                next(iter(speculative.values()))[0] if speculative else ""
            )
            if spec_draft == "ngram":
                spec_source = "ngram"
            elif spec_draft.startswith("cross:"):
                spec_source = "cross"
                spec_draft = spec_draft.split(":", 1)[1]
            else:
                spec_source = "model"
            return FakeBackend(
                spec_k=spec_k,
                spec_source=spec_source,
                **(
                    {"spec_draft": spec_draft}
                    if spec_draft and spec_source != "ngram"
                    else {}
                ),
                spec_acceptance=float(
                    os.environ.get("FAKE_SPEC_ACCEPTANCE", "1.0")
                ),
                spec_sampled_acceptance=(
                    float(os.environ["FAKE_SPEC_SAMPLED_ACCEPTANCE"])
                    if "FAKE_SPEC_SAMPLED_ACCEPTANCE" in os.environ
                    else None
                ),
                spec_accept_floor=spec_accept_floor,
                prefix_share=prefix_share,
                prefix_store_hbm_bytes=prefix_store_hbm_bytes,
                prefix_store_host_bytes=prefix_store_host_bytes,
                joules_per_token=float(
                    os.environ.get("FAKE_JOULES_PER_TOKEN", "0.0")
                ),
            )
        if backend_kind == "jax-tp":
            from ..parallel.mesh import MeshSpec, build_mesh
            from ..parallel.tp import TensorParallelEngine

            # --dp M grows a dp axis next to tp (ISSUE 19): stepped
            # sessions shard their carry's row dim (and page pool) over
            # it, so idle mesh devices serve rows instead of replicating
            mesh_spec = (
                MeshSpec.dp_tp(dp, tp) if dp > 1 else MeshSpec.tp_only(tp)
            )
            return TensorParallelEngine(
                mesh=build_mesh(mesh_spec),
                decode_attention="auto",
                hf_checkpoints=hf_checkpoints or None,
                quantize=quantize,
                kv_quantize=kv_quantize,
                paged_kv=paged_kv,
                speculative=speculative or None,
                spec_accept_floor=spec_accept_floor or 0.0,
                **(
                    {"spec_temperature_max": spec_temperature_max}
                    if spec_temperature_max is not None
                    else {}
                ),
                **(
                    {"spec_draft_temperature": spec_draft_temperature}
                    if spec_draft_temperature is not None
                    else {}
                ),
                prefix_cache_size=prefix_cache,
                prefix_share=prefix_share,
                **(
                    {"prefix_index_entries": prefix_index_entries}
                    if prefix_index_entries is not None
                    else {}
                ),
                **(
                    {"prefix_store_hbm_bytes": prefix_store_hbm_bytes}
                    if prefix_store_hbm_bytes is not None
                    else {}
                ),
                **(
                    {"prefix_store_host_bytes": prefix_store_host_bytes}
                    if prefix_store_host_bytes is not None
                    else {}
                ),
            )
        if backend_kind == "jax":
            from ..engine.jax_engine import JaxEngine

            return JaxEngine(
                decode_attention="auto",
                hf_checkpoints=hf_checkpoints or None,
                quantize=quantize,
                kv_quantize=kv_quantize,
                paged_kv=paged_kv,
                speculative=speculative or None,
                spec_accept_floor=spec_accept_floor or 0.0,
                **(
                    {"spec_temperature_max": spec_temperature_max}
                    if spec_temperature_max is not None
                    else {}
                ),
                **(
                    {"spec_draft_temperature": spec_draft_temperature}
                    if spec_draft_temperature is not None
                    else {}
                ),
                prefix_cache_size=prefix_cache,
                prefix_share=prefix_share,
                **(
                    {"prefix_index_entries": prefix_index_entries}
                    if prefix_index_entries is not None
                    else {}
                ),
                **(
                    {"prefix_store_hbm_bytes": prefix_store_hbm_bytes}
                    if prefix_store_hbm_bytes is not None
                    else {}
                ),
                **(
                    {"prefix_store_host_bytes": prefix_store_host_bytes}
                    if prefix_store_host_bytes is not None
                    else {}
                ),
            )
        raise CommandError(f"serve: unknown backend {backend_kind!r}")

    if models is None and backend_kind != "fake":
        from ..models.config import MODEL_REGISTRY

        models = sorted(MODEL_REGISTRY)
    if replicas > 1:
        # Replica fleet behind the front-door router (ISSUE 12): N
        # fully independent backend+scheduler pairs in this process;
        # real multi-host deployments run one `serve` per host and
        # attach them with `serve-fleet --targets`.
        from ..serve.router import LocalReplica, Router, RouterServer

        sched_kwargs = {
            k: v
            for k, v in {
                "max_batch": max_batch,
                "budget_aware": budget_aware,
                "slice_steps": slice_steps,
                "prefill_chunk_tokens": prefill_chunk_tokens,
                "ttft_slo_ms": ttft_slo_ms,
                "spec_accept_floor": spec_accept_floor,
                "preempt_policy": preempt_policy,
                "preempt_max_wait_s": preempt_max_wait_s,
            }.items()
            if v is not None
        }
        if batch_window_ms > 0:
            sched_kwargs["window_s"] = batch_window_ms / 1e3
        def replica_role(i: int) -> str:
            if roles:
                return roles[i % len(roles)]
            return role or "mixed"

        def build_replica(i: int) -> LocalReplica:
            backend = build_backend()
            if model_policy is not None:
                # each replica hosts its OWN multi-model fleet (ISSUE
                # 15): per-model lanes over that replica's engine; the
                # router treats the whole fleet as one replica
                from ..serve.model_fleet import ModelFleetScheduler

                return LocalReplica(
                    f"r{i}",
                    backend,
                    scheduler=ModelFleetScheduler(
                        backend,
                        models=models,
                        model_policy=model_policy,
                        escalate_max_tokens=escalate_max_tokens,
                        **sched_kwargs,
                    ),
                    role=replica_role(i),
                )
            return LocalReplica(
                f"r{i}", backend, role=replica_role(i), **sched_kwargs
            )

        fleet = [build_replica(i) for i in range(replicas)]
        router = Router(
            fleet,
            policy=route_policy or "least-queue",
            **(
                {"probe_interval_s": probe_interval_ms / 1e3}
                if probe_interval_ms is not None
                else {}
            ),
        )
        RouterServer(
            router,
            host=host,
            port=DEFAULT_PORT if port is None else port,
            models=models,
            default_priority=default_priority,
            slo=slo,
        ).serve_forever()
        return
    server = GenerationServer(
        build_backend(),
        host=host,
        port=DEFAULT_PORT if port is None else port,
        models=models,
        batch_window_ms=batch_window_ms,
        max_batch=max_batch,
        budget_aware=budget_aware,
        access_log=access_log,
        scheduler=scheduler,
        slice_steps=slice_steps,
        prefill_chunk_tokens=prefill_chunk_tokens,
        ttft_slo_ms=ttft_slo_ms,
        spec_accept_floor=spec_accept_floor,
        default_priority=default_priority,
        preempt_policy=preempt_policy,
        preempt_max_wait_s=preempt_max_wait_s,
        model_policy=model_policy,
        escalate_max_tokens=escalate_max_tokens,
        slo=slo,
        role=role,
        usage_ledger_dir=usage_ledger_dir,
    )
    server.serve_forever()


def serve_fleet_command(args: List[str]) -> None:
    """Front-door router over ALREADY-RUNNING replica servers: each
    ``--targets`` entry is one ``serve`` process (any backend) reached
    over the wire — the multi-host deployment shape; ``serve
    --replicas N`` is the in-process (single-host / CI) twin."""
    port = None
    host = "0.0.0.0"
    targets: List[str] = []
    models: Optional[List[str]] = None
    route_policy = None
    probe_interval_ms = None
    default_priority = None
    slo = None
    it = iter(args)
    for arg in it:
        if arg == "--port":
            port = int(next(it, "11434"))
        elif arg == "--host":
            host = next(it, "0.0.0.0")
        elif arg == "--targets":
            targets = [t for t in next(it, "").split(",") if t]
        elif arg == "--slo":
            from ..obs.slo import parse_slo_spec

            slo = next(it, "")
            try:
                parse_slo_spec(slo)
            except ValueError as exc:
                raise CommandError(f"serve-fleet: --slo: {exc}")
        elif arg == "--models":
            models = [m for m in next(it, "").split(",") if m]
        elif arg == "--route-policy":
            from ..serve.router import ROUTE_POLICIES

            route_policy = next(it, "")
            if route_policy not in ROUTE_POLICIES:
                raise CommandError(
                    "serve-fleet: --route-policy expects one of "
                    + "|".join(ROUTE_POLICIES)
                )
        elif arg == "--probe-interval-ms":
            probe_interval_ms = float(next(it, "0")) or None
        elif arg == "--default-priority":
            from ..serve.protocol import parse_priority

            try:
                default_priority = parse_priority(next(it, ""))
            except ValueError as exc:
                raise CommandError(f"serve-fleet: --default-priority: {exc}")
        else:
            raise CommandError(f"serve-fleet: unrecognised option {arg!r}")
    if not targets:
        raise CommandError(
            "serve-fleet: --targets host:port[,host:port...] is required"
        )
    from ..serve.protocol import DEFAULT_PORT
    from ..serve.router import RemoteReplica, Router, RouterServer

    fleet = []
    for i, target in enumerate(targets):
        url = target if target.startswith("http") else f"http://{target}"
        fleet.append(RemoteReplica(f"r{i}", url))
    router = Router(
        fleet,
        policy=route_policy or "least-queue",
        **(
            {"probe_interval_s": probe_interval_ms / 1e3}
            if probe_interval_ms is not None
            else {}
        ),
    )
    RouterServer(
        router,
        host=host,
        port=DEFAULT_PORT if port is None else port,
        models=models,
        default_priority=default_priority,
        slo=slo,
    ).serve_forever()


def analyze_command(
    experiment_dir: Path, filter_scope: str = "cell"
) -> None:
    """Standalone analysis pass (reference equivalent: opening the R notebook
    on run_table.csv, data-analysis/analysis-visualization.ipynb).
    ``filter_scope`` picks the IQR strata: the default ``cell`` is finer
    than the notebook's procedure; ``subset`` reproduces the notebook's
    exact order (ADVICE round-4: the divergent default must be a visible
    choice, not a silent one — the report header says which ran)."""
    if not (experiment_dir / "run_table.csv").exists():
        raise CommandError(f"no run_table.csv under {experiment_dir}")
    from ..analysis.pipeline import analyze_experiment

    report = analyze_experiment(
        experiment_dir, make_plots=True, filter_scope=filter_scope
    )
    term.log_ok(
        f"analysis written to {experiment_dir}/analysis_report.md "
        f"({report['n_after_iqr']}/{report['n_rows']} rows after IQR, "
        f"filter scope: {filter_scope})"
    )


def prepare() -> None:
    """Environment self-check (the reference's ``prepare`` is an empty stub,
    CLIRegister.py:77-78)."""
    term.log(f"python: {sys.version.split()[0]}")
    try:
        import jax

        term.log_ok(f"jax {jax.__version__}; devices: {jax.devices()}")
    except Exception as exc:  # noqa: BLE001
        term.log_warn(f"jax unavailable: {exc}")
    from ..profilers.energy_probe import probe_energy_channels

    # The cooldown promise is derived from the channels the study's
    # profilers actually CONSUME, not from raw probe kinds: rapl feeds
    # RaplEnergyProfiler/NativeHostProfiler and hwmon/battery feed
    # SysfsPowerProfiler (host, every mode); tpu_info feeds
    # TpuPowerCounterProfiler and libtpu_monitoring's duty cycle feeds
    # TpuDutyCycleProfiler (device, in-process only — and duty counts
    # as measured even though its probe kind is "utilization"). A
    # future channel the probe learns about before a profiler consumes
    # it lands in the unconsumed note below rather than inflating the
    # promise (code-review round-4 finding).
    HOST_CONSUMED = {"rapl", "hwmon", "battery"}
    DEVICE_CONSUMED = {"tpu_info", "libtpu_monitoring"}
    measured_host = False
    measured_device = False
    unconsumed = []
    for status in probe_energy_channels():
        line = f"energy channel {status.name} ({status.kind}/{status.scope}): {status.detail}"
        if status.available:
            term.log_ok(line)
            if status.name in HOST_CONSUMED:
                measured_host = True
            elif status.name in DEVICE_CONSUMED:
                measured_device = True
            else:
                unconsumed.append(status.name)
        else:
            term.log_warn(line)
    if unconsumed:
        term.log_warn(
            f"channel(s) {', '.join(unconsumed)} are live but no profiler "
            "consumes them yet - they appear in energy_channels.json only "
            "and do not change the study's cooldown policy"
        )
    # The channel audit decides the study's thermal policy — say which
    # way it will go BEFORE a sweep is launched (VERDICT round-3
    # directive 7), per scope: host channels (RAPL/native sampler) wire
    # in every mode, but device channels are skipped in HTTP-client mode
    # (on_device_url), where the serving process owns the chip — the
    # promise must match what LlmEnergyConfig will actually do.
    from ..experiments.llm_energy import LlmEnergyConfig

    cool_measured = LlmEnergyConfig.MEASURED_CHANNEL_COOLDOWN_MS // 1000
    cool_modelled = LlmEnergyConfig.MODELLED_ONLY_COOLDOWN_MS // 1000
    if measured_host:
        term.log_ok(
            "measured HOST energy channel present - studies wire it in "
            "every mode, record real host Joules, and use the "
            f"reference's {cool_measured} s thermal cooldown "
            "(docs/ARCHITECTURE.md: measured-host runbook)"
        )
    elif measured_device:
        term.log_ok(
            "measured DEVICE energy channel present - in-process/serving "
            f"studies wire it ({cool_measured} s thermal cooldown); a "
            "pure HTTP-client study (on_device_url set) leaves device "
            "channels to the serving process and runs modelled-only at "
            f"{cool_modelled} s (docs/ARCHITECTURE.md: measured-host "
            "runbook)"
        )
    else:
        term.log_warn(
            "no measured energy source on this host - studies will record "
            "modelled Joules (energy_model_J), say so in "
            "energy_channels.json, and drop the cooldown to "
            f"{cool_modelled} s (modelled energy is thermal-state-free); "
            "on a host with RAPL/tpu-info/libtpu-monitoring the same "
            "study re-runs with measured Joules unchanged "
            "(docs/ARCHITECTURE.md: measured-host runbook)"
        )


def main(argv: Optional[List[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args or args[0] in ("help", "--help", "-h"):
        print(HELP)
        return 0
    cmd = args[0]
    try:
        if cmd == "config-create":
            config_create(Path(args[1]) if len(args) > 1 else None)
        elif cmd == "analyze":
            if len(args) < 2:
                raise CommandError("analyze requires an experiment directory")
            scope = "cell"
            rest = args[2:]
            while rest:
                if rest[0] == "--filter-scope":
                    if len(rest) < 2 or rest[1] not in (
                        "cell",
                        "subset",
                        "pooled",
                    ):
                        raise CommandError(
                            "analyze: --filter-scope expects "
                            "cell|subset|pooled"
                        )
                    scope = rest[1]
                    rest = rest[2:]
                else:
                    raise CommandError(f"analyze: unknown flag {rest[0]!r}")
            analyze_command(Path(args[1]), filter_scope=scope)
        elif cmd == "recompute-energy":
            if len(args) < 2:
                raise CommandError(
                    "recompute-energy requires an experiment directory"
                )
            from ..experiments.llm_energy import recompute_energy

            # --chips loc=n[,loc=n...]: fallback chip map for tables from
            # before the per-row `chips` column (rows carrying the column
            # always win)
            chips = None
            quantize = None
            trust_remote_timings = False
            rest = args[2:]
            while rest:
                flag = rest[0]
                if flag == "--trust-remote-timings":
                    # pre-backend-column tables only: disable the
                    # remote-rows-were-aliased assumption so genuinely
                    # multi-chip remote measurements keep their own
                    # windows (the warning recompute_energy emits names
                    # this flag's library twin)
                    trust_remote_timings = True
                    rest = rest[1:]
                    continue
                if flag == "--chips":
                    if len(rest) < 2:
                        raise CommandError(
                            "recompute-energy: --chips expects loc=n[,loc=n...]"
                        )
                    chips = {}
                    for entry in rest[1].split(","):
                        loc, _, count = entry.partition("=")
                        if not loc or not count.isdigit():
                            raise CommandError(
                                "recompute-energy: --chips expects "
                                "loc=n[,loc=n...]"
                            )
                        chips[loc] = int(count)
                elif flag == "--quantize":
                    if len(rest) < 2:
                        raise CommandError(
                            "recompute-energy: --quantize expects "
                            "model=mode[,model=mode...]"
                        )
                    quantize = {}
                    valid_modes = ("bf16", "int8", "int4", "int4-i32")
                    for entry in rest[1].split(","):
                        model, sep, mode = entry.partition("=")
                        if not model or not sep or not mode:
                            raise CommandError(
                                "recompute-energy: --quantize expects "
                                "model=mode[,model=mode...]"
                            )
                        # an unknown mode would silently be billed at
                        # int4 width by the bytes accounting — refuse
                        if mode not in valid_modes:
                            raise CommandError(
                                f"recompute-energy: unknown quantize mode "
                                f"{mode!r} for {model!r}; expected one of "
                                f"{', '.join(valid_modes)}"
                            )
                        quantize[model] = mode
                else:
                    raise CommandError(
                        f"recompute-energy: unknown flag {flag!r}"
                    )
                rest = rest[2:]
            n = recompute_energy(
                Path(args[1]),
                n_chips_by_location=chips,
                quantize_by_model=quantize,
                assume_aliased_without_backend=not trust_remote_timings,
            )
            term.log_ok(
                f"recomputed modelled energy for {n} rows from their "
                f"persisted raw measurements; analysis re-run"
            )
        elif cmd == "prepare":
            prepare()
        elif cmd == "serve":
            serve_command(args[1:])
        elif cmd == "serve-fleet":
            serve_fleet_command(args[1:])
        elif cmd.endswith(".py"):
            run_config_file(Path(cmd))
        else:
            raise CommandError(f"unrecognised command: {cmd!r}\n{HELP}")
    except CommandError as exc:
        term.log_fail(str(exc))
        return 2
    except ExperimentError as exc:
        term.log_fail(f"{type(exc).__name__}: {exc}")
        return 1
    return 0
