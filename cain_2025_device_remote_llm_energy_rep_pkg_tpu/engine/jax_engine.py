"""The JAX/XLA generation engine: jit prefill + ``lax.scan`` decode.

Replaces the reference's Ollama server (experiment/RunnerConfig.py:128-131)
with an in-process TPU-native engine:

- Weights random-init straight into HBM as bfloat16 (see models/transformer).
- Prompts pad to power-of-two buckets and generation lengths round up to
  buckets, so the number of distinct compilations is O(log max_len) — the
  anti-recompilation discipline SURVEY.md §7 lists as risk #3.
- The decode loop is a single ``lax.scan`` over the token budget: no
  per-token Python, no host↔device chatter inside the loop; EOS is handled
  with a done-mask so shapes stay static.
- An optional ``decode_attention`` kernel (the Pallas one) can be injected;
  default is the fused-by-XLA jnp path.

Timings split prefill vs decode via ``block_until_ready`` fences — the
reference can only clock the whole curl subprocess.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.config import MODEL_REGISTRY, ModelConfig, get_model_config
from ..obs.metrics import REGISTRY as _OBS, enabled as _obs_enabled
from ..obs.trace import TRACER as _TRACER
from ..models.transformer import (
    DecodeAttentionFn,
    PrefillAttentionFn,
    Transformer,
    forward,
    logits_for,
)
from ..ops.sampling import sample_token
from ..profilers.tpu import chip_peaks_for
from ..utils.device import device_report, on_tpu
from .backend import (
    GenerationBackend,
    GenerationChunk,
    GenerationRequest,
    GenerationResult,
    UnsupportedMechanism,
)

PROMPT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)
GEN_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)
# generate_batch rows pad up to these. Decode reads the weights once a
# step for all rows, so more rows a step is more tokens a weight read
# (by how much: not measured on the chip); what bounds a sub-batch is
# KV-cache MEMORY, not a fixed row count, so generate_batch picks the
# widest bucket whose estimated cache fits BATCH_KV_BUDGET_BYTES instead
# of hard-capping at 32.
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# Budget for one sub-batch's K+V caches (the dominant per-row memory).
# Default 2.5 GB: sized so short shapes (cache_len 320-ish) run 128
# rows in ONE decode loop while a max-context fleet still splits to
# BATCH_MIN_SPLIT_ROWS. Not derived from the attached chip's bytes_limit
# yet (ROADMAP D6).
BATCH_KV_BUDGET_BYTES = int(
    os.environ.get("BATCH_KV_BUDGET_BYTES", 2_500_000_000)
)
# Never split below this width whatever the estimate says — the old hard
# cap, known-safe at max context on the flagship.
BATCH_MIN_SPLIT_ROWS = 32
# Monotonic id stamped into every batch result's extras["decode_window"]
# so consumers (bench.py) can count DISTINCT decode windows explicitly
# instead of deduplicating decode_s floats — float identity silently
# miscounts if two sequential windows collide or rows ever get per-row
# finalized windows.
_DECODE_WINDOW_IDS = itertools.count()
# Paged stacked decode: at/above this STATIC batch width the engine
# computes the prompt parts with the fused-XLA variant (or, where it
# reads the pool in place and the pool has the kernel's form, the
# held-page kernel) instead of the Pallas parts kernel, whose (B, Hkv,
# Jmax) grid costs per cell — linear in rows. The default is 1 (always):
# the cells of BENCHMARK.json with a bfloat16 pool of whole heads read
# `impl: pallas-pool`, the latent ones `xla-pool`; the table kernel
# against the other two at the cells' shapes: not measured on the chip
# (ROADMAP D5). The table kernel
# remains the TP-mesh path (its shard_map rule) and the
# injectable/parity anchor.
PAGED_XLA_PARTS_MIN_ROWS = int(
    os.environ.get("PAGED_XLA_PARTS_MIN_ROWS", 1)
)
# ...but not when the page table is WIDE: the XLA variant reads the
# whole pool (or, where pages are shared, gathers Jmax pages for EVERY
# row: the longest row taxes all), while the kernel's per-cell skip
# bounds each row's work by its own pages. Where the two cross: not
# measured on the chip (no cell has a table wider than 4; PERF.md §7
# row 3 is the cell that would judge it). The default of 8 pages is 1k
# tokens of spread; env-overridable.
PAGED_XLA_PARTS_MAX_JMAX = int(
    os.environ.get("PAGED_XLA_PARTS_MAX_JMAX", 8)
)


def paged_parts_impl(rows: int, table_width: int, pool: Any = None) -> str:
    """Which per-layer stacked-paged parts implementation serves a
    decode step of ``rows`` rows over a ``table_width``-wide page table
    — ``"xla"`` (fused XLA), ``"pallas-pool"`` (the held-page kernel,
    ``ops/pallas_paged_attention.py::pallas_paged_pool_parts``) or
    ``"pallas"`` (the page-table kernel) — by the two gates above and
    the pool's form. ``pool`` is the stacked pool leaf of a step that
    reads it in place, pages with one reader each and not a latent pool
    (None otherwise): where it has the held-page kernel's form
    (``paged_pool_supported``: bfloat16, not int8 KV) on a TPU, in a
    trace that is not a sharded engine's (the kernel has no
    partitioning rule), the kernel walks the held pages in XLA's place.
    The ONE rule: the attention closure selects with it and
    ``/debug/state`` reports it (:meth:`JaxEngine._paged_decode_impl`,
    which also says how the XLA variant names its pages)."""
    from ..models.quantize import unpartitioned_kernels_enabled
    from ..ops.pallas_paged_attention import paged_pool_supported

    if (
        rows >= PAGED_XLA_PARTS_MIN_ROWS
        and table_width <= PAGED_XLA_PARTS_MAX_JMAX
    ):
        if (
            pool is not None
            and on_tpu()
            and unpartitioned_kernels_enabled()
            and paged_pool_supported(pool)
        ):
            return "pallas-pool"
        return "xla"
    return "pallas"


DEFAULT_STREAM_CHUNK = 32  # decode steps per streamed chunk
# Decode steps per slice of a STEPPED (iteration-level) decode session
# (engine/stepped.py): the scheduler regains control between slices to
# retire finished rows (freeing their pages mid-flight) and admit queued
# requests into the freed rows. Smaller slices = finer admission
# granularity but more host round-trips per generated token; 8–16 keeps
# the per-slice host sync under ~5% of slice wall on the measured tiny
# shapes while bounding a joiner's wait to one slice.
DECODE_SLICE_STEPS = int(os.environ.get("DECODE_SLICE_STEPS", 16))

# Engine telemetry (obs): the fence-timed prefill/decode windows the
# engine already measures, published as metric families + spans. The
# (path, kv) labels name the attention-path the step actually ran —
# contiguous/paged cache × bf16/int8 KV — so a scrape can tell WHICH
# cache representation produced a latency/J figure without re-deriving
# it from CLI flags.
_PREFILL_H = _OBS.histogram(
    "llm_engine_prefill_seconds",
    "Wall time of one prefill window (solo request or grouped rows)",
)
_DECODE_H = _OBS.histogram(
    "llm_engine_decode_seconds",
    "Wall time of one decode window (solo request or shared batch)",
)
_TOKENS_C = _OBS.counter(
    "llm_engine_generated_tokens_total",
    "Generated tokens, by attention path and KV representation",
    labels=("path", "kv"),
)
_STEPS_C = _OBS.counter(
    "llm_engine_decode_steps_total",
    "Decode-loop steps executed, by attention path and KV representation",
    labels=("path", "kv"),
)
_TOKS_PER_S_G = _OBS.gauge(
    "llm_engine_tokens_per_s",
    "Aggregate tokens/s of the most recent decode window",
    labels=("path", "kv"),
)


def _to_host_list(arr) -> "list":
    """One batched device→host transfer (never per-element int() reads —
    each is its own blocking device read)."""
    import numpy as np

    return np.asarray(arr).tolist()


def _stepped_donation() -> Dict[str, Any]:
    """``jax.jit`` kwargs donating the stepped carry argument — on
    accelerator backends only. XLA:CPU silently accepts the aliasing
    request but reuses donated buffers unsoundly under async dispatch:
    with the carry donated, a mid-flight join's eager page scatter
    intermittently corrupted a COMPANION row's pool pages (token-parity
    divergence right after the join, ~1-in-3 full-suite runs on the
    8-virtual-device CPU harness; never on the default no-donation CPU
    path). That scatter was seen when a join wrote its pages eagerly;
    since PR 29 a join's install is one jitted program
    (``_row_install_jit``) that takes these same kwargs, so on the CPU
    it copies the carry as the slice step does, and the warning now
    covers both programs. On TPU the donation is the point: the output
    carry aliases the input buffers, the KV pool never holds 2× liveness
    across a slice, and a join writes its pages in place."""
    if jax.default_backend() == "cpu":
        return {}
    return {"donate_argnums": (1,)}


def _bucket(n: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


# Prompts longer than the largest bucket prefill in chunks of this size
# (the flash-prefill kernel supports offset > 0 against a partially-filled
# cache), so max prompt length is bounded by max_seq_len, not the bucket.
PREFILL_CHUNK = PROMPT_BUCKETS[-1]
# Token budget for ONE chunk of a mid-flight join's prefill
# (engine/stepped.py join_begin/join_step): the continuous scheduler
# interleaves join-prefill chunks with decode slices, so in-flight rows'
# stall per slice is bounded by this many prompt tokens instead of the
# joiner's whole prompt length. 0 = auto (256: the chunk forward stays
# in the same ballpark as a 16-step decode slice on the measured shapes
# while reusing an existing compiled prompt bucket). CLI twin:
# `serve --prefill-chunk-tokens`.
JOIN_PREFILL_CHUNK_TOKENS = (
    int(os.environ.get("PREFILL_CHUNK_TOKENS", 0)) or 256
)


def _floor_bucket(n: int, buckets: Tuple[int, ...]) -> int:
    """Largest bucket <= n (the smallest bucket when n undershoots all)
    — chunk-budget rounding must round DOWN so a stall budget is a cap,
    where _bucket's round-up would exceed it."""
    best = buckets[0]
    for b in buckets:
        if b <= n:
            best = b
    return best


def _prompt_chunks(
    s_real: int, chunk: Optional[int] = None
) -> "list[tuple[int, int]]":
    """Cover ``s_real`` prompt tokens as [(start, bucket), ...]: full
    ``chunk``-sized chunks (default PREFILL_CHUNK), then one
    bucket-rounded tail. ``chunk`` must be a PROMPT_BUCKETS width so
    every chunk reuses an existing compiled prefill shape."""
    if chunk is None:
        chunk = PREFILL_CHUNK
    chunks = []
    start = 0
    while s_real - start > chunk:
        chunks.append((start, chunk))
        start += chunk
    chunks.append((start, _bucket(s_real - start, PROMPT_BUCKETS)))
    return chunks


def _prompt_alloc(s_real: int) -> int:
    """Cache slots the prompt needs (last chunk's end, bucket-rounded) —
    equals ``_bucket(s_real, PROMPT_BUCKETS)`` for single-chunk prompts."""
    start, bucket = _prompt_chunks(s_real)[-1]
    return start + bucket


def _apply_stop(tokens: "list[int]", text: str, tok, stop) -> "tuple[list[int], str]":
    """Cut output before the first occurrence of any stop string (Ollama's
    ``options.stop``): text cut exactly; tokens cut at the smallest prefix
    whose decode covers the kept text. Decode length is approximately
    monotone in the prefix length, so the cut binary-searches (O(log n)
    decode calls, not O(n)); tokenizers whose decode is not prefix-stable
    (HF cleanup/joining) make the token cut best-effort — the returned
    *text* is always exact and authoritative."""
    cuts = [text.find(s) for s in stop if s in text]
    if not cuts:
        return tokens, text
    kept = text[: min(cuts)]
    lo, hi = 0, len(tokens)
    while lo < hi:
        mid = (lo + hi) // 2
        if len(tok.decode(tokens[:mid])) < len(kept):
            lo = mid + 1
        else:
            hi = mid
    # Bounded linear fix-up: cleanup/merging tokenizers are only
    # *approximately* monotone, so the bisect can land a position or two
    # off; scan the neighbourhood for the true smallest covering prefix at
    # O(1) extra decodes so token counts (eval_count on the wire) stay
    # exact wherever a covering prefix exists.
    for j in range(max(0, lo - 2), min(len(tokens), lo + 2) + 1):
        if len(tok.decode(tokens[:j])) >= len(kept):
            lo = j
            break
    return tokens[:lo], kept


def _spec_margin(k: int) -> int:
    """Extra KV-cache slots the speculative path needs beyond the usual
    buckets (rounds overshoot by up to k; the draft seats one extra entry),
    rounded up to the 128-lane tile the Pallas kernels require. Single
    source of truth for the routing fit-check and the allocation."""
    return -(-(2 * k + 2) // 128) * 128


def _dir_signature(path: str) -> str:
    """Cheap content signature of a checkpoint dir: latest mtime_ns + bytes."""
    import os

    latest, total = 0, 0
    for root, _, files in os.walk(path):
        for f in files:
            try:
                st = os.stat(os.path.join(root, f))
            except OSError:
                continue
            latest = max(latest, st.st_mtime_ns)
            total += st.st_size
    return f"{latest}:{total}"


class JaxEngine(GenerationBackend):
    """In-process generation over the model registry.

    ``registry`` maps model name → ModelConfig; pass tiny() configs for
    hermetic tests. ``decode_attention`` lets callers swap in the Pallas
    kernel ('auto' uses it on TPU platforms, None forces the jnp path).
    """

    def __init__(
        self,
        registry: Optional[Dict[str, ModelConfig]] = None,
        dtype: jnp.dtype = jnp.bfloat16,
        decode_attention: "str | DecodeAttentionFn | None" = "auto",
        seed: int = 0,
        weight_cache_dir: "Optional[str]" = None,
        quantize: "str | Dict[str, Optional[str]] | None" = None,
        hf_checkpoints: Optional[Dict[str, str]] = None,
        prefill_attention: "str | PrefillAttentionFn | None" = "auto",
        speculative: "Optional[Dict[str, Tuple[str, int]]]" = None,
        spec_accept_floor: float = 0.0,  # stepped-session auto-fallback
        spec_temperature_max: float = 2.0,  # sampled-spec eligibility cap
        spec_draft_temperature: Optional[float] = None,  # draft-q flatten
        prefix_cache_size: int = 0,  # cached prompt-KV entries per model
        prefix_cache_bytes: Optional[int] = None,  # total KV bytes cap
        kv_quantize: Optional[str] = None,  # None | "int8" (decode path)
        paged_kv: bool = False,  # batched decode over a paged pool
        page_size: int = 128,
        prefix_share: bool = False,  # shared-prefix CoW paging + store
        prefix_index_entries: int = 16,  # prefix-store node cap (per model)
        prefix_store_hbm_bytes: Optional[int] = None,  # store HBM budget
        prefix_store_host_bytes: Optional[int] = None,  # store host budget
        prefix_store_scope: str = "engine",  # "engine" | "session"
    ) -> None:
        # quantize: one mode for every model (None | "int8" | "int4"), or a
        # per-model dict {model: mode} with an optional "default" key — a
        # sweep can then serve small models at int8 (speed) and large ones
        # at int4 (capacity) from ONE engine, like Ollama's per-model GGUF
        # quant choices.
        valid_modes = (None, "int8", "int4", "int4-i32")
        if isinstance(quantize, dict):
            for name, mode in quantize.items():
                if mode not in valid_modes:
                    raise ValueError(
                        f"unsupported quantize mode for {name!r}: {mode!r}"
                    )
        elif quantize not in valid_modes:
            raise ValueError(f"unsupported quantize mode: {quantize!r}")
        if prefix_cache_size < 0:
            raise ValueError(
                f"prefix_cache_size must be >= 0, got {prefix_cache_size}"
            )
        if prefix_cache_bytes is not None and prefix_cache_bytes < 0:
            raise ValueError(
                f"prefix_cache_bytes must be >= 0, got {prefix_cache_bytes}"
            )
        # kv_quantize="int8": the DECODE loop runs over an int8 KV cache
        # (per-position vector scales; prefill fills a bf16 cache which is
        # quantized once before decoding). Halves the cache stream — the
        # dominant per-step bytes for many-KV-head models at long context
        # (phi3: ~0.8 GB/step at 2k). Composes with generate/stream/batch,
        # the TP engine, paged_kv (int8 page pool), the prefix caches
        # (both the solo LRU and the session prefix index store/seed
        # PRE-quantization bf16 — the int8×prefix exclusion retired in
        # ISSUE 7) AND speculative decoding (ISSUE 9 retires the last
        # standing exclusion: the TARGET cache is int8 — the verify block
        # quantizes its k+1 entries with the same per-vector scale math a
        # step-at-a-time decode would, so accepted tokens see
        # bit-identical cache state — while the DRAFT cache stays at the
        # engine dtype: it is tiny, and quantizing it would buy nothing).
        if kv_quantize not in (None, "int8"):
            raise ValueError(f"unsupported kv_quantize mode: {kv_quantize!r}")
        # paged_kv=True: generate_batch decodes over a shared page pool
        # (engine/paged_kv.py) instead of one max-shape contiguous cache —
        # each row holds exactly ceil(tokens/page) pages, so mixed-length
        # concurrent requests stop paying the widest row's padding. The
        # pool is assembled per batch (stateless); prefill stays
        # contiguous per request and is scattered in whole pages.
        # COMPOSES with kv_quantize="int8": the pool then holds int8
        # pages (codes + per-position scales pooled together) and the
        # stacked side caches quantize their writes, so a mixed-length
        # fleet decodes out of a ~4× denser cache (2× int8 × ~per-row
        # pages vs widest-row padding) — the two capacity features
        # target the same workload and no longer exclude each other
        # (VERDICT round-5 directives #3/#4).
        if page_size < 1 or page_size % 128:
            raise ValueError(
                f"page_size must be a positive multiple of 128 (the lane "
                f"width the decode kernel tiles on), got {page_size}"
            )
        self.paged_kv = paged_kv
        self.page_size = page_size
        self.kv_quantize = kv_quantize
        # The peaks row every energy estimate of this engine is billed
        # against. Resolved here, before any model loads: a TPU that is
        # not in the table raises (profilers.tpu.UnknownChipError)
        # rather than being billed another chip's watts.
        attached = device_report()
        self.chip = chip_peaks_for(attached["platform"], attached["kind"])
        # prefix_share=True: the ENGINE owns a persistent cross-session
        # prefix store (engine/radix_store.py, ISSUE 14) — a token-id
        # radix tree over refcounted pool pages with host-RAM spill.
        # Stepped sessions consult and publish to it: joiners whose
        # prompt shares a published prefix map its refcounted read-only
        # pool pages and chunk-prefill only the divergent tail (CoW on
        # the boundary page) — including joiners in a FRESH session
        # after the publisher's session (and its pool) died, and after
        # a scheduler restart. Works on all four cache layouts; page
        # sharing engages on the paged pools, seed-only reuse on
        # contiguous. CLI twin: `serve --prefix-share`
        # (+ --prefix-index-entries / --prefix-store-hbm-bytes /
        # --prefix-store-host-bytes).
        self.prefix_share = bool(prefix_share)
        if prefix_index_entries < 1:
            raise ValueError(
                f"prefix_index_entries must be >= 1, got {prefix_index_entries}"
            )
        self.prefix_index_entries = int(prefix_index_entries)
        for knob, value in (
            ("prefix_store_hbm_bytes", prefix_store_hbm_bytes),
            ("prefix_store_host_bytes", prefix_store_host_bytes),
        ):
            if value is not None and int(value) < 0:
                raise ValueError(f"{knob} must be >= 0, got {value}")
        self.prefix_store = None
        if self.prefix_share:
            from .radix_store import RadixPrefixStore

            self.prefix_store = RadixPrefixStore(
                capacity=self.prefix_index_entries,
                hbm_bytes=prefix_store_hbm_bytes,
                host_bytes=prefix_store_host_bytes,
                scope=prefix_store_scope,
            )
        self.quantize = quantize
        # target model → DraftSpec(source, draft, k): eligible requests
        # for the target route through speculative decoding
        # (engine/speculative.py). Accepted value forms per target:
        # ("small", 4) — small-model autoregressive draft; ("ngram", 4)
        # — prompt-lookup drafting, zero extra weights;
        # ("cross:small", 4) — cross-model drafting on another serving
        # lane's resident model (ISSUE 16). A "default" key applies one
        # spec to EVERY served target (the `serve --speculative
        # <draft>[:k]` draft-only form); a model never self-drafts
        # through the default (pure overhead; ngram has no draft model
        # so it applies everywhere).
        from .speculative import DraftSpec

        def _norm_spec(value) -> DraftSpec:
            if isinstance(value, DraftSpec):
                return value
            draft, k = value
            if draft == "ngram":
                return DraftSpec("ngram", None, int(k))
            if isinstance(draft, str) and draft.startswith("cross:"):
                return DraftSpec("cross", draft.split(":", 1)[1], int(k))
            return DraftSpec("model", draft, int(k))

        self.speculative = {
            name: _norm_spec(value)
            for name, value in (speculative or {}).items()
        }
        # Stepped-session adaptive policy (engine/stepped.py): when the
        # rolling measured acceptance of a speculating session drops
        # below this fraction, the session falls back to plain decode
        # (speculation is LOSING there: every round pays k draft steps +
        # a k+1-wide verify for ~1 emitted token). 0 = never fall back.
        if not 0.0 <= float(spec_accept_floor) < 1.0:
            raise ValueError(
                f"spec_accept_floor must be in [0, 1), got {spec_accept_floor}"
            )
        self.spec_accept_floor = float(spec_accept_floor)
        # Sampled-spec eligibility cap (ISSUE 16): requests with
        # temperature in (0, spec_temperature_max] speculate via the
        # rejection-resampling lane; hotter requests serve plain (the
        # modified distributions flatten toward uniform there and
        # acceptance collapses — pure overhead). 0 restores the PR-9
        # greedy-only gate.
        if float(spec_temperature_max) < 0.0:
            raise ValueError(
                f"spec_temperature_max must be >= 0, got "
                f"{spec_temperature_max}"
            )
        self.spec_temperature_max = float(spec_temperature_max)
        # Independent draft proposal temperature (ISSUE 18): sampled
        # rows' draft sources propose at this temperature instead of
        # the row's own — the accept math stays exact for any proposal
        # distribution (q is computed from the same modified chain the
        # proposals were drawn from), so this is a pure acceptance-rate
        # tuning knob. None = draft at the row's temperature (classic).
        # Must be strictly positive when set: a zero draft temperature
        # would degenerate q at the modified-probs stage.
        if spec_draft_temperature is not None and not (
            float(spec_draft_temperature) > 0.0
        ):
            raise ValueError(
                f"spec_draft_temperature must be > 0 when set, got "
                f"{spec_draft_temperature}"
            )
        self.spec_draft_temperature = (
            float(spec_draft_temperature)
            if spec_draft_temperature is not None
            else None
        )
        # Per-SOURCE acceptance memory (ISSUE 16): recent fallback
        # acceptances keyed "source:draft". n-gram acceptance collapses
        # on non-repetitive text; learning the window per source keys
        # lets ngram sessions stop re-arming speculation without
        # dragging model-draft sessions down with them. Sessions append
        # on fallback (engine/stepped.py::_spec_fall_back) and clear on
        # healthy close; _init_spec consults it before arming.
        self._spec_source_health: Dict[str, list] = {}
        # Optional fleet hook (serve/model_fleet.py): maps a DRAFT model
        # name to its live J/token so fully-rejected cross-model rounds
        # bill honest draft Joules into the wasted-energy ledger.
        self.spec_draft_jpt: Optional[Callable[[str], Optional[float]]] = None
        # model name → local HF checkpoint dir; load_model converts the
        # trained weights (models/convert.py) instead of random-initialising
        # (the analogue of Ollama's pulled model store, README.md:29-31).
        self.hf_checkpoints = dict(hf_checkpoints or {})
        self.registry = dict(registry) if registry is not None else dict(MODEL_REGISTRY)
        self.dtype = dtype
        self.seed = seed
        # Optional on-disk weight cache (SURVEY.md §5: resume shouldn't
        # re-initialise weights; equivalent of Ollama's model store).
        self._weight_cache = None
        if weight_cache_dir:
            from .checkpoint import WeightCache

            self._weight_cache = WeightCache(weight_cache_dir)
        self._tokenizers: Dict[str, Any] = {}  # per-model, via _tokenizer_for
        # prompt-prefix KV reuse (off by default: the energy study wants
        # every run to pay its own prefill); model → OrderedDict LRU of
        # ids-tuple → (k_cache, v_cache, last-position logits, lru_stamp).
        # Budgeted by BYTES, not just entries: cached KV is device memory
        # (tens–hundreds of MB per entry on 7B models) and counts against
        # the same allocation budget as resident weights.
        self.prefix_cache_size = prefix_cache_size
        self.prefix_cache_bytes = prefix_cache_bytes
        # Either cap enables the cache: entries (per model), bytes (global),
        # or both. A byte cap alone must not be silently inert.
        self._prefix_enabled = (
            prefix_cache_size > 0 or prefix_cache_bytes is not None
        )
        self._prefix_cache: Dict[str, Any] = {}
        self._prefix_clock = 0  # global LRU stamp across models
        self._models: Dict[str, Transformer] = {}
        # Models whose weights exist ONLY in memory (install_model — no
        # registry-init or checkpoint source to reload from): never LRU
        # victims, or a later load would silently re-randomise them.
        self._pinned: set = set()
        # Live stepped-session refcount per model (ISSUE 15): a model
        # with live decode rows must never be an LRU eviction victim —
        # its carry references the weights the eviction would drop.
        # SteppedDecodeSession.open/close pair _session_opened/_closed.
        self._live_sessions: Dict[str, int] = {}
        # Live energy attribution (ISSUE 13/15): the engine-wide figure
        # router probes read, plus the PER-MODEL split the multi-model
        # fleet's cheapest-joules policy ranks on.
        self.last_joules_per_token: Optional[float] = None
        self.last_joules_per_token_by_model: Dict[str, float] = {}
        self._prefill_cache: Dict[Tuple, Callable] = {}
        self._decode_cache: Dict[Tuple, Callable] = {}
        # whoever places the persistent cache (serve CLI, a bench), the
        # engine's programs are keyed on their scope names too: a trace
        # must name operations by THIS tree's scopes, not by those of the
        # tree that filled the cache (utils/compile_cache.py)
        from ..utils.compile_cache import key_cache_on_metadata

        key_cache_on_metadata()
        self._warmed: set = set()
        # "auto" = the MEASURED-best policy per cache representation
        # (round-4 chip A/Bs, docs/PERF.md "attention impl selection"):
        # plain bf16 decode uses XLA's fused attention — it TIES the
        # Pallas decode kernel single-stream (327 vs 325 tok/s short,
        # 354 vs 324 long) and is ~2× faster batched (6.3k vs 3.7k
        # aggregate at 32 rows) — while the int8-KV and paged paths keep
        # their kernels on TPU (fused dequant / no gather materialise,
        # each measured better than its fallback).
        self._auto_attention = decode_attention == "auto"
        if decode_attention == "auto":
            decode_attention = None
        self.decode_attention: Optional[DecodeAttentionFn] = decode_attention  # type: ignore[assignment]
        # Independent of the decode kernel choice: "auto" (default) uses the
        # Pallas flash prefill on TPU backends, None forces the jnp path.
        if prefill_attention == "auto":
            prefill_attention = self._auto_prefill_attention()
        self.prefill_attention: Optional[PrefillAttentionFn] = prefill_attention  # type: ignore[assignment]

    def _specialised_kernels_enabled(self) -> bool:
        """Whether the cache-specialised kernels (int8-KV, paged) engage:
        an explicitly injected decode kernel opts in anywhere; "auto"
        engages them on TPU backends only (their fallbacks are the right
        CPU/test path)."""
        return self.decode_attention is not None or (
            self._auto_attention and on_tpu()
        )

    @staticmethod
    def _auto_prefill_attention():
        if on_tpu():
            from ..ops.pallas_attention import pallas_prefill_attention

            return pallas_prefill_attention
        return None

    # -- model management -----------------------------------------------------
    def _quant_mode(self, model: str) -> Optional[str]:
        """The weight-quantization mode for ``model`` (see ctor)."""
        if isinstance(self.quantize, dict):
            return self.quantize.get(model, self.quantize.get("default"))
        return self.quantize

    def load_model(self, model: str) -> None:
        if model in self._models:
            # refresh LRU recency (dicts preserve insertion order; the
            # eviction policy pops from the front)
            self._models[model] = self._models.pop(model)
            return
        cfg = (
            self.registry[model]
            if model in self.registry
            else get_model_config(model)
        )
        # Eviction first: on allocation-scoped budgets the resident-sum
        # fail-fast would otherwise reject loads the LRU eviction exists
        # to make possible.
        self._refuse_unsupported(model, cfg)
        self._ensure_allocation_capacity(model, cfg)
        self._check_memory_budget(model, cfg)
        quant_mode = self._quant_mode(model)
        t0 = time.monotonic()
        ckpt_dir = self.hf_checkpoints.get(model)
        if ckpt_dir is not None:

            def make_full():
                from ..models.convert import load_hf_pretrained

                return load_hf_pretrained(ckpt_dir, cfg, dtype=self.dtype)

        else:

            def make_full():
                from ..models.transformer import init_params

                return init_params(cfg, jax.random.PRNGKey(self.seed), self.dtype)

        if quant_mode is None:
            make_params = make_full
        elif ckpt_dir is None:

            def make_params():
                # One jitted program that inits AND quantizes per leaf: XLA
                # buffer liveness frees each full-precision leaf (and the
                # rng's f32 intermediates, which fuse away) before the next
                # allocates, so the chip never holds the full-precision
                # model — llama3.1:8b bf16 alone fills a 16 GB chip; the
                # whole point of quantizing is that it doesn't fit
                # otherwise.
                from ..models.quantize import quantize_leaf
                from ..models.transformer import init_params

                @jax.jit
                def build(key):
                    return init_params(
                        cfg,
                        key,
                        self.dtype,
                        post=lambda name, leaf: quantize_leaf(
                            name, leaf, quant_mode
                        ),
                    )

                return jax.block_until_ready(
                    build(jax.random.PRNGKey(self.seed))
                )

        else:

            def make_params():
                # HF checkpoints materialise fully during conversion; route
                # through the CPU backend and ship only the quantized
                # tensors to the accelerator.
                from ..models.quantize import quantize_params

                cpu = jax.devices("cpu")[0]
                with jax.default_device(cpu):
                    p = quantize_params(make_full(), mode=quant_mode)
                # device_put with no target is an identity for arrays
                # already committed to a device — name the accelerator.
                return jax.device_put(p, jax.devices()[0])

        if self._weight_cache is not None:
            import hashlib

            # The fingerprint keys the checkpoint to this exact architecture
            # + dtype + weight source; a tiny() test config, a dtype change,
            # or a different HF checkpoint dir must not restore a mismatched
            # pytree. HF sources also include a content signature (latest
            # mtime + total size — computed only here, when a cache could
            # serve stale weights) so an in-place re-download or fine-tune
            # at the same path misses the cache.
            source = (
                f"hf:{ckpt_dir}|{_dir_signature(ckpt_dir)}"
                if ckpt_dir is not None
                else "init"
            )
            fingerprint = hashlib.sha256(
                f"{cfg!r}|{jnp.dtype(self.dtype).name}|{source}"
                f"|quant:{quant_mode}".encode()
            ).hexdigest()[:12]
            params = self._weight_cache.get_or_init(
                model, self.seed, make_params, fingerprint=fingerprint
            )
            tf = Transformer(cfg=cfg, params=params)
        else:
            tf = Transformer(cfg=cfg, params=make_params())
        jax.block_until_ready(tf.params)
        self._load_s = time.monotonic() - t0
        self._models[model] = tf
        self._observe_model_loaded(model, load_s=self._load_s)

    def _refuse_unsupported(self, model: str, cfg: ModelConfig) -> None:
        """At load, name what ``cfg``'s layers do not run with yet
        (ROADMAP "What the program cannot run"): a latent cache (one
        compressed row a token and block, no V leaf) has no int8 form, no
        shared-prefix pages and no speculative verify; a mesh is refused
        by the partition rules themselves (``parallel/sharding.py``). A
        session refuses preemption bundles (:meth:`SteppedDecodeSession.
        preempt`), which migration rides."""
        if not (cfg.latent or cfg.blocks_per_layer > 1 or cfg.state_layers):
            return
        refused = {
            "kv_quantize": bool(self.kv_quantize),
            # a state-space layer's prefix is a state SNAPSHOT, which
            # neither the page store nor the contiguous prompt cache holds
            "prefix_share": self.prefix_share
            or bool(cfg.state_layers and self._prefix_enabled),
            "speculative": self._resolve_spec(model) is not None
            or any(spec.draft == model for spec in self.speculative.values()),
        }
        mesh = getattr(self, "mesh", None)
        if mesh is not None:
            # the one mesh refusal is where the partition rules live;
            # asked here so that it comes before the weights are built
            from ..parallel.sharding import param_specs

            param_specs(cfg, mesh)
        for mechanism, asked in refused.items():
            if asked:
                raise UnsupportedMechanism(
                    mechanism, model,
                    "its state-space layers keep a recurrent state a row "
                    "beside the attention layers' cache; int8 rows, "
                    "prefix snapshots and a verify block's rollback are "
                    "not built for it"
                    if cfg.state_layers
                    else "its cache is one latent row a token and attention "
                    "block; int8 rows, shared-prefix pages and "
                    "speculative verify blocks are not built for it",
                )

    @staticmethod
    def _refuse_contiguous_rows(model: str, cfg: ModelConfig) -> None:
        """Several rows of a model with state-space layers decode together
        in a PAGED session only: the recurrent state is a leaf of that
        session's carry beside the page pool, and neither the contiguous
        batch cache's row assembly nor a contiguous session's row install
        knows the record. (One row alone, ``generate``, runs contiguous.)"""
        if cfg.state_layers:
            raise UnsupportedMechanism(
                "contiguous_session", model,
                "a model with state-space layers batches its rows in a "
                "paged session (paged_kv=True): the recurrent state rides "
                "beside the page pool, not in a contiguous batch cache",
            )

    def _check_memory_budget(self, model: str, cfg: ModelConfig) -> None:
        """Fail fast — with the estimated bytes, the probed budget, and the
        remedy — instead of an opaque RESOURCE_EXHAUSTED from XLA minutes
        into a load (or hours into a sweep). The budget source hierarchy
        lives in utils/memory.py; unknown budget (CPU tests) skips the
        check."""
        from ..utils.memory import (
            ModelMemoryError,
            device_memory_budget,
            estimate_weight_bytes,
        )

        budget = device_memory_budget()
        if budget is None:
            return
        n_dev = max(1, getattr(self, "n_devices", 1))
        dtype_b = jnp.dtype(self.dtype).itemsize
        mode = self._quant_mode(model)
        # A sharded engine (TP) splits the weights over its mesh. Models
        # already resident count too — a 7-model sweep accumulates unless
        # the workload unloads between models.
        est = estimate_weight_bytes(cfg, mode, dtype_b) // n_dev
        resident = sum(
            estimate_weight_bytes(tf.cfg, self._quant_mode(name), dtype_b)
            // n_dev
            for name, tf in self._models.items()
        )
        if est + resident > budget:
            if mode is None:
                hint = "quantize (int8 halves, int4 quarters the bytes)"
            elif mode == "int8":
                hint = "quantize to int4 or shard over a mesh (TensorParallelEngine)"
            else:
                hint = "shard over more devices (tensor/pipeline parallelism)"
            if resident:
                hint += (
                    f"; or unload_all() first ({len(self._models)} models, "
                    f"~{resident / 1024**3:.2f} GiB, already resident)"
                )
            raise ModelMemoryError(model, est + resident, budget, hint)

    def install_model(
        self, model: str, cfg: ModelConfig, params: Dict[str, Any]
    ) -> None:
        """Serve externally produced weights (a trained checkpoint from
        ``parallel.train`` / ``models.tiny_lm``, or any converted pytree)
        under ``model`` — the engine-side analogue of dropping a model into
        Ollama's store. Applies the engine's quantization mode, registers
        the config, and skips ``load_model``'s init path entirely.
        Re-installing an existing name evicts every cache derived from the
        old weights/config (prefix KV, compiled fns, warm markers)."""
        self._evict_model_state(model)
        self._ensure_allocation_capacity(model, cfg)
        self._check_memory_budget(model, cfg)
        mode = self._quant_mode(model)
        if mode is not None:
            from ..models.quantize import quantize_params

            params = quantize_params(params, mode=mode)
        self.registry[model] = cfg
        self._models[model] = Transformer(cfg=cfg, params=params)
        self._pinned.add(model)
        self._observe_model_loaded(model)

    def _ensure_allocation_capacity(self, model: str, cfg: ModelConfig) -> None:
        """Ollama-style LRU model eviction: total HBM holds only a few
        models (the 7-model sweep's weights sum to ~22 GiB), so before a
        load that would overflow the device's ALLOCATION budget, evict the
        least-recently-used models' *weights*. Compiled executables, warm
        markers and tokenizers are kept — they capture configs, not
        params — so a later request for an evicted model reloads in
        seconds (persistent-compile-cache-backed init) instead of paying
        the full compile again."""
        from ..runner import term
        from ..utils.memory import (
            LOAD_TRANSIENT_HEADROOM_BYTES,
            device_allocation_budget,
            estimate_weight_bytes,
        )

        budget = device_allocation_budget()
        if budget is None or not self._models:
            return
        n_dev = max(1, getattr(self, "n_devices", 1))
        dtype_b = jnp.dtype(self.dtype).itemsize

        def weight_bytes(name: str, c: ModelConfig) -> int:
            return estimate_weight_bytes(c, self._quant_mode(name), dtype_b) // n_dev

        incoming = weight_bytes(model, cfg) + LOAD_TRANSIENT_HEADROOM_BYTES
        resident = {
            name: weight_bytes(name, tf.cfg) for name, tf in self._models.items()
        }
        # Cached prompt KV is device memory too (tens–hundreds of MB per
        # entry on 7B models) and counts against the same budget. Prefix
        # entries evict FIRST — they are pure recompute, far cheaper to
        # rebuild than a model reload. Charged per device like the weights
        # (nbytes of a mesh-sharded array is its GLOBAL size).
        prefix_resident = self._prefix_bytes() // n_dev
        while sum(resident.values()) + prefix_resident + incoming > budget:
            if prefix_resident > 0:
                freed_global = self._evict_prefix_lru()
                if freed_global:
                    prefix_resident -= freed_global // n_dev
                    term.log(
                        f"evicted a cached prompt prefix "
                        f"(~{freed_global / n_dev / 1024**2:.1f} MiB/device) "
                        f"to fit {model}"
                    )
                    continue
                prefix_resident = 0
            # oldest (LRU) un-pinned model; installed-only weights have no
            # source to reload from and are never victims. Models with
            # LIVE stepped rows are never victims either (ISSUE 15):
            # their session carries reference the weights, so eviction
            # is DEFERRED until the session drains — the next load's
            # capacity pass retries, and _check_memory_budget (when a
            # budget is known) turns an unservable load into a clean
            # refusal instead of undefined decode behavior.
            victim = next(
                (
                    n
                    for n in self._models
                    if n not in self._pinned and not self._live_sessions.get(n)
                ),
                None,
            )
            if victim is None:
                live = [
                    n
                    for n in self._models
                    if n not in self._pinned and self._live_sessions.get(n)
                ]
                if live:
                    from ..obs.metrics import MODEL_EVICT_DEFERRED_C
                    from ..obs.metrics import enabled as _enabled

                    if _enabled():
                        MODEL_EVICT_DEFERRED_C.inc()
                    term.log(
                        f"deferring weight eviction for {model}: "
                        f"{', '.join(live)} hold(s) live stepped rows"
                    )
                break
            freed = resident.pop(victim)
            self._evict_weights(victim)
            term.log(
                f"evicted {victim} weights (~{freed / 1024**3:.2f} GiB) to "
                f"fit {model}; compiled state kept, reload is cheap"
            )

    def _evict_weights(self, model: str, reason: str = "lru") -> None:
        """Drop a model's weights (and its prefix-cache K/V — device
        arrays) but KEEP compiled fns/warm markers/tokenizer: the config
        is unchanged, so a reload serves them unmodified."""
        evicted = self._models.pop(model, None) is not None
        self._prefix_cache.pop(model, None)
        if evicted:
            self._observe_model_evicted(model, reason)

    def _evict_model_state(self, model: str) -> None:
        """Drop every per-model derivative: compiled prefill/decode fns
        (their closures capture the old cfg/eos), prefix-cache KV (computed
        from the old weights), warm markers, the tokenizer, and the model
        itself. Keys are tuples whose elements include the model name
        (plain, 'batch'- and 'spec'-prefixed; spec entries also name the
        draft)."""
        evicted = self._models.pop(model, None) is not None
        self._pinned.discard(model)
        self._tokenizers.pop(model, None)
        self._prefix_cache.pop(model, None)
        for cache in (self._prefill_cache, self._decode_cache):
            for key in [k for k in cache if model in k]:
                del cache[key]
        self._warmed = {k for k in self._warmed if model not in k}
        if evicted:
            self._observe_model_evicted(model, "reinstall")

    def unload_all(self) -> None:
        for model in list(self._models):
            self._observe_model_evicted(model, "unload")
        self._models.clear()
        self._pinned.clear()
        self._prefill_cache.clear()
        self._decode_cache.clear()
        self._tokenizers.clear()
        self._prefix_cache.clear()
        self._warmed.clear()  # a fresh load must re-warm outside the window

    # -- weight-lifecycle observability + session guards (ISSUE 15) ------------
    def model_weight_bytes(self, model: str) -> int:
        """Estimated resident weight bytes of ``model`` under this
        engine's quantization rules — a pure estimate off the config
        (loaded or not); the multi-model fleet's size ordering (its
        small-first policy and cheapest-joules fallback) ranks on it."""
        from ..utils.memory import estimate_weight_bytes

        if model in self._models:
            cfg = self._models[model].cfg
        elif model in self.registry:
            cfg = self.registry[model]
        else:
            cfg = get_model_config(model)
        return estimate_weight_bytes(
            cfg, self._quant_mode(model), jnp.dtype(self.dtype).itemsize
        )

    def _observe_model_loaded(
        self, model: str, load_s: Optional[float] = None
    ) -> None:
        """Weight-lifecycle telemetry for one load/install: residency
        gauges + the ``model_loaded`` flight event, trace-linked to the
        request that triggered the load when one is current. Telemetry
        must never fail a load."""
        if not _obs_enabled():
            return
        try:
            from ..obs.flight import EV_MODEL_LOADED, FLIGHT, trace_attrs
            from ..obs.metrics import observe_model_loaded
            from ..obs.trace import TRACER

            nbytes = self.model_weight_bytes(model)
            observe_model_loaded(model, nbytes)
            FLIGHT.emit(
                EV_MODEL_LOADED,
                model=model,
                weight_bytes=nbytes,
                **({"load_s": round(load_s, 4)} if load_s is not None else {}),
                **trace_attrs(TRACER.current()),
            )
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _observe_model_evicted(self, model: str, reason: str) -> None:
        if not _obs_enabled():
            return
        try:
            from ..obs.flight import EV_MODEL_EVICTED, FLIGHT, trace_attrs
            from ..obs.metrics import observe_model_evicted
            from ..obs.trace import TRACER

            observe_model_evicted(model, reason)
            FLIGHT.emit(
                EV_MODEL_EVICTED,
                model=model,
                reason=reason,
                **trace_attrs(TRACER.current()),
            )
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _session_opened(self, model: str) -> None:
        """A stepped session holds live rows of ``model``: pin its
        weights against LRU eviction until :meth:`_session_closed`."""
        self._live_sessions[model] = self._live_sessions.get(model, 0) + 1

    def _session_closed(self, model: str) -> None:
        n = self._live_sessions.get(model, 0) - 1
        if n > 0:
            self._live_sessions[model] = n
        else:
            self._live_sessions.pop(model, None)

    def live_sessions(self, model: str) -> int:
        """Open stepped sessions currently holding rows of ``model``
        (the eviction-guard refcount — 0 means eviction is allowed)."""
        return self._live_sessions.get(model, 0)

    def models_debug_state(self) -> "Dict[str, Any]":
        """The weight-lifecycle block of ``GET /debug/state``: resident
        models with their estimated bytes and live-session refcounts."""
        out: Dict[str, Any] = {"loaded": {}, "pinned": sorted(self._pinned)}
        for name in self.loaded_models():
            try:
                nbytes = self.model_weight_bytes(name)
            except Exception:  # noqa: BLE001 — estimate only
                nbytes = None
            out["loaded"][name] = {
                "weight_bytes": nbytes,
                "live_sessions": self._live_sessions.get(name, 0),
                "joules_per_token": self.last_joules_per_token_by_model.get(
                    name
                ),
            }
        return out

    def loaded_models(self) -> "list[str]":
        # dict.copy() is C-atomic under the GIL: a safe snapshot even while
        # another request thread is loading a model.
        return sorted(self._models.copy())

    def _tokenizer_for(self, model: str):
        """The model's own tokenizer when served from an HF checkpoint
        (ids line up with the trained embeddings, text is real text); the
        byte fallback otherwise."""
        if model not in self._tokenizers:
            from ..models.tokenizer import load_tokenizer

            self._tokenizers[model] = load_tokenizer(
                self.hf_checkpoints.get(model)
            )
        return self._tokenizers[model]

    def _place_cache(self, k_cache, v_cache, cfg: ModelConfig):
        """Placement hook: the TP engine overrides this to shard the KV cache
        over the mesh; the single-device engine leaves it on the default
        device."""
        return k_cache, v_cache

    def warmup(self, request: GenerationRequest) -> None:
        """Compile this request's prefill/decode buckets outside any
        measurement window (once per (model, buckets, top_k) shape)."""
        key = (
            request.model,
            _prompt_alloc(
                len(self._tokenizer_for(request.model).encode(request.prompt))
            ),
            _bucket(request.max_new_tokens, GEN_BUCKETS),
            request.top_k,
            request.top_p < 1.0,
            request.repeat_penalty != 1.0,
        )
        if key in self._warmed:
            return
        self.generate(request)
        # Also compile the chunk-bucket decode the streaming path uses, so a
        # first stream:true request doesn't pay XLA compilation inside the
        # measured window either.
        for _ in self.generate_stream(request):
            pass
        self._warmed.add(key)

    # -- compiled stages ------------------------------------------------------
    def _prefill_attention_for(
        self, cfg: ModelConfig
    ) -> Optional[PrefillAttentionFn]:
        """The prefill attention impl for ``cfg``'s compiled prefill. The
        TP engine overrides: a Mosaic kernel cannot be partitioned by
        GSPMD, so on a mesh it runs under ``shard_map`` or not at all.
        Latent attention runs plain XLA attention in its absorbed form (no
        kernel takes keys wider than its values)."""
        return None if cfg.latent else self.prefill_attention

    def _prefill_fn(self, model: str, s_bucket: int, cache_len: int) -> Callable:
        key = (model, s_bucket, cache_len)
        if key in self._prefill_cache:
            return self._prefill_cache[key]
        tf = self._models[model]
        cfg = tf.cfg
        prefill_attention = self._prefill_attention_for(cfg)

        @jax.jit
        def prefill(params, tokens, offset, last_index, k_cache, v_cache):
            """``offset`` > 0 = a later chunk of a long prompt (earlier
            chunks' K/V already sit in the cache)."""
            hidden, k_cache, v_cache = forward(
                params, cfg, tokens, offset, k_cache, v_cache,
                None, prefill_attention,
                # a recurrence that runs over the bucket's pad tokens is
                # wrong for good: a state-space model's state (it rides
                # in k_cache's record) stands still past the last real one
                **(
                    {
                        "token_mask": jnp.arange(tokens.shape[1])[None, :]
                        <= last_index[:, None]
                    }
                    if cfg.state_layers
                    else {}
                ),
            )
            last_hidden = jnp.take_along_axis(
                hidden, last_index[:, None, None].astype(jnp.int32), axis=1
            )[:, 0]
            logits = logits_for(params, cfg, last_hidden)
            return logits, k_cache, v_cache

        self._prefill_cache[key] = prefill
        return prefill

    def _decode_fn(
        self,
        model: str,
        n_steps: int,
        top_k: int,
        use_top_p: bool = False,
        use_rp: bool = False,
    ) -> Callable:
        """``use_top_p``/``use_rp`` are static: they gate whether the vocab
        sort (nucleus) and the presence-mask scatter (repeat penalty) exist
        in the compiled loop at all, so requests that don't use them pay
        nothing."""
        key = (model, n_steps, top_k, use_top_p, use_rp)
        if key in self._decode_cache:
            return self._decode_cache[key]
        tf = self._models[model]
        cfg = tf.cfg
        decode_attention = self._decode_attention_for_cache(cfg)
        eos = self._tokenizer_for(model).eos_id

        @jax.jit
        def decode(
            params,
            first_token,
            start_offset,
            k_cache,
            v_cache,
            temperature,
            rng,
            n_real,
            top_p,
            repeat_penalty,
            presence,
        ):
            """Runs exactly ``n_real`` steps (≤ the compiled bucket ``n_steps``)
            and stops early when every sequence hit EOS — so the measured
            decode window never pays for unrequested tokens. ``n_real`` is
            traced; one compiled fn serves every length in the bucket."""
            b = first_token.shape[0]

            def cond(carry):
                _, _, _, _, _, done, i, _, _ = carry
                return (i < n_real) & ~jnp.all(done)

            def body(carry):
                token, offset, kc, vc, rng, done, i, out, pres = carry
                hidden, kc, vc = forward(
                    params, cfg, token[:, None], offset, kc, vc, decode_attention,
                    **({"token_mask": ~done[:, None]} if cfg.state_layers else {}),
                )
                logits = logits_for(params, cfg, hidden[:, 0])
                rng, sub = jax.random.split(rng)
                nxt = sample_token(
                    logits,
                    sub,
                    temperature,
                    top_k,
                    top_p if use_top_p else None,
                    pres if use_rp else None,
                    repeat_penalty if use_rp else None,
                )
                nxt = jnp.where(done, jnp.int32(eos), nxt)
                done = done | (nxt == eos)
                if use_rp:
                    pres = pres.at[jnp.arange(b), nxt].set(True)
                out = out.at[:, i].set(nxt)
                return (nxt, offset + 1, kc, vc, rng, done, i + 1, out, pres)

            out0 = jnp.full((b, n_steps), eos, dtype=jnp.int32)
            init = (
                first_token,
                start_offset,
                k_cache,
                v_cache,
                rng,
                jnp.zeros((b,), dtype=bool),
                jnp.int32(0),
                out0,
                presence,
            )
            (_, _, kc, vc, rng_out, _, n_done, out_tokens, presence_out) = (
                jax.lax.while_loop(cond, body, init)
            )
            return out_tokens, n_done, kc, vc, presence_out, rng_out

        self._decode_cache[key] = decode
        return decode

    def _decode_attention_for_cache(
        self, cfg: Optional[ModelConfig] = None
    ) -> Optional[DecodeAttentionFn]:
        """The decode kernel matching the cache representation: the int8
        variant unpacks the quantized cache's codes+scales (folding the
        scales into the online softmax — the fallback would materialise a
        dequantized cache); without it (CPU tests) the jnp fallback in
        the model handles both. Round 4 gated out non-128-multiple head
        dims (phi3's 96) after a trace abort on real hardware — round 5
        traced that abort to the kernel's rank-3 scales BlockSpec, which
        Mosaic rejected for EVERY int8-KV shape, not to the head dim.
        With scales shipped as [B,Hkv,T,1] the kernel lowers and runs at
        d_head 96/128 across 1–128 rows (docs/kernel_lowering.jsonl; the
        kernel zero-pads the head dim internally), so phi3-class models
        — the KV-heavy targets kv-quantize exists for — now get the
        kernel instead of the dequantizing fallback."""
        if cfg is not None and cfg.latent:
            return None  # plain XLA attention over the latent rows
        if not self.kv_quantize:
            return self.decode_attention
        if not self._specialised_kernels_enabled():
            return None

        from ..ops.pallas_attention import pallas_decode_attention_int8

        def int8_cache_attention(q, kc, vc, lengths):
            return pallas_decode_attention_int8(
                q, kc["q"], kc["s"], vc["q"], vc["s"], lengths
            )

        return int8_cache_attention

    def _quantize_batch_cache(self, model: str, k_cache, v_cache):
        """One bulk quantization of a batch's assembled cache: scales are
        per (layer, row, head, position), so rows stay independent and each
        row's stream is bit-identical to its single-request quantized
        decode. Hook point — the TP engine overrides to also place the
        {"q","s"} leaves on its mesh (same reason as _maybe_quantize_cache)."""
        from ..models.quantize import quantize_kv_cache

        return quantize_kv_cache(k_cache, v_cache)

    def _maybe_quantize_cache(self, st: Dict[str, Any]) -> Dict[str, Any]:
        """Post-prefill cache conversion for the decode loop (prefill
        always runs on the bf16 cache; see kv_quantize in the ctor)."""
        if self.kv_quantize:
            from ..models.quantize import quantize_kv_cache

            st["k_cache"], st["v_cache"] = quantize_kv_cache(
                st["k_cache"], st["v_cache"]
            )
        return st

    # -- generation -----------------------------------------------------------
    def _run_prefill(
        self, model: str, prompt_ids: "list[int]", cache_len: int
    ):
        """Build + place the KV cache and prefill the prompt — in one
        compiled call for prompts within the largest bucket, else in
        PREFILL_CHUNK-sized chunks at increasing offsets. Shared by _start
        (target) and the speculative path's draft prefill so the mechanics
        live in one place. Returns the final chunk's last-position logits.

        With ``prefix_cache_size`` > 0, the KV of previously prefilled
        prompts is kept (LRU per model) and the longest cached entry that
        is an exact prefix of this prompt seeds the cache — a device-side
        copy instead of recompute, the standard system-prompt win."""
        tf = self._models[model]
        tok = self._tokenizer_for(model)
        s_real = len(prompt_ids)
        k_cache, v_cache = tf.init_cache(1, cache_len, dtype=self.dtype)
        k_cache, v_cache = self._place_cache(k_cache, v_cache, tf.cfg)
        logits = None

        covered = 0
        hit = self._find_prefix(model, prompt_ids)
        if hit is not None:
            hit_ids, hit_k, hit_v, hit_logits = hit
            p = len(hit_ids)
            # The remaining tokens re-chunk from `covered`, and the tail
            # chunk's bucket rounding must not write past cache_len (the
            # underlying dynamic_update_slice would CLAMP the start and
            # silently overwrite valid prefix K/V). Use less of the hit if
            # needed so the chunk end always fits.
            while p > 0 and p < s_real and (
                p + _prompt_alloc(s_real - p) > cache_len
            ):
                p -= 1
            if p > 0:
                # copy the cached prefix region into the fresh cache
                # (cache_len may differ between requests; positions are
                # what matter)
                k_cache = jax.lax.dynamic_update_slice(
                    k_cache, hit_k[:, :, :, :p, :], (0, 0, 0, 0, 0)
                )
                v_cache = jax.lax.dynamic_update_slice(
                    v_cache, hit_v[:, :, :, :p, :], (0, 0, 0, 0, 0)
                )
                covered = p
                logits = hit_logits  # only used when the hit covers everything

        if covered < s_real:
            remaining = prompt_ids[covered:]
            for start, bucket in _prompt_chunks(len(remaining)):
                ids = remaining[start : start + bucket]
                real = len(ids)
                tokens = jnp.asarray(
                    [ids + [tok.pad_id] * (bucket - real)], dtype=jnp.int32
                )
                prefill = self._prefill_fn(model, bucket, cache_len)
                logits, k_cache, v_cache = prefill(
                    tf.params,
                    tokens,
                    jnp.int32(covered + start),
                    jnp.asarray([real - 1]),
                    k_cache,
                    v_cache,
                )

        self._store_prefix(model, prompt_ids, k_cache, v_cache, logits, s_real)
        return logits, k_cache, v_cache

    # -- prefix cache ---------------------------------------------------------
    def _find_prefix(self, model: str, prompt_ids: "list[int]"):
        """Longest cached (ids, k, v, logits) whose ids are a prefix of
        ``prompt_ids``; refreshes its LRU position."""
        if not self._prefix_enabled:
            return None
        entries = self._prefix_cache.get(model)
        if not entries:
            return None
        best_key = None
        n = len(prompt_ids)
        for key in entries:
            if len(key) <= n and list(key) == prompt_ids[: len(key)]:
                if best_key is None or len(key) > len(best_key):
                    best_key = key
        if best_key is None:
            return None
        entries.move_to_end(best_key)
        k, v, logits, _ = entries[best_key]
        self._prefix_clock += 1
        entries[best_key] = (k, v, logits, self._prefix_clock)
        return list(best_key), k, v, logits

    @staticmethod
    def _prefix_entry_bytes(entry) -> int:
        k, v, logits, _stamp = entry
        return k.nbytes + v.nbytes + (logits.nbytes if logits is not None else 0)

    def _prefix_bytes(self) -> int:
        """Total device bytes pinned by cached prompt KV, all models."""
        return sum(
            self._prefix_entry_bytes(e)
            for entries in self._prefix_cache.values()
            for e in entries.values()
        )

    def _evict_prefix_lru(self) -> int:
        """Drop the globally least-recently-used prefix entry; returns the
        bytes freed (0 when the cache is empty)."""
        best = None
        for model, entries in self._prefix_cache.items():
            for key, entry in entries.items():
                if best is None or entry[3] < best[0]:
                    best = (entry[3], model, key)
        if best is None:
            return 0
        _, model, key = best
        freed = self._prefix_entry_bytes(self._prefix_cache[model].pop(key))
        if not self._prefix_cache[model]:
            del self._prefix_cache[model]
        return freed

    def _store_prefix(self, model, prompt_ids, k_cache, v_cache, logits, s_real):
        if not self._prefix_enabled:
            return
        from collections import OrderedDict

        entries = self._prefix_cache.setdefault(model, OrderedDict())
        key = tuple(prompt_ids)
        # Store only the prompt's own positions — the generation region and
        # bucket padding would pin HBM a hit never reads. JAX arrays are
        # immutable, so keeping references is safe (decode produces new
        # arrays and never mutates these).
        self._prefix_clock += 1
        entries[key] = (
            k_cache[:, :, :, :s_real],
            v_cache[:, :, :, :s_real],
            logits,
            self._prefix_clock,
        )
        entries.move_to_end(key)
        while self.prefix_cache_size and len(entries) > self.prefix_cache_size:
            entries.popitem(last=False)
        # Byte cap across ALL models' entries: evict globally-LRU entries
        # until under the cap. A lone entry larger than the cap is dropped
        # outright — caching it would defeat the budget it enforces.
        if self.prefix_cache_bytes is not None:
            while (
                self._prefix_bytes() > self.prefix_cache_bytes
                and self._evict_prefix_lru()
            ):
                pass

    def _start(
        self,
        request: GenerationRequest,
        cache_len: Optional[int] = None,
        prompt_ids: "Optional[list[int]]" = None,
    ) -> Dict[str, Any]:
        """The shared prefill path: tokenize, bucket, run prefill and sample
        the first token. Returns the decode state that :meth:`generate` (one
        monolithic decode call), :meth:`generate_stream` (chunked decode
        calls) and :meth:`generate_batch` (rows concatenated into one
        batched decode) continue from. ``cache_len`` overrides the KV cache
        size so a batch's rows can share one common cache shape;
        ``prompt_ids`` skips re-tokenizing when the caller already encoded
        the prompt."""
        self.load_model(request.model)
        tf = self._models[request.model]
        cfg = tf.cfg

        tok = self._tokenizer_for(request.model)
        if prompt_ids is None:
            prompt_ids = tok.encode(request.prompt)
        if not prompt_ids:
            # An HF tokenizer with no BOS token + an empty prompt yields
            # zero ids; prefill would then gather "last-position" logits
            # from an all-pad chunk and sample garbage. Fail cleanly (the
            # server maps ValueError to a 400).
            raise ValueError(
                f"{request.model}: prompt encodes to zero tokens (empty "
                "prompt and the tokenizer adds no BOS); provide a non-empty "
                "prompt"
            )
        s_real = len(prompt_ids)
        s_bucket = _prompt_alloc(s_real)
        g_bucket = _bucket(request.max_new_tokens, GEN_BUCKETS)
        if cache_len is None:
            cache_len = s_bucket + g_bucket
        if cache_len > cfg.max_seq_len:
            raise ValueError(
                f"{request.model}: prompt bucket {s_bucket} + generation "
                f"bucket {g_bucket} exceeds max_seq_len {cfg.max_seq_len}; "
                "shorten the prompt or max_new_tokens"
            )

        use_top_p = request.top_p < 1.0
        use_rp = request.repeat_penalty != 1.0

        # The presence mask (repeat penalty) covers prompt + generated
        # tokens, like Ollama's default repeat_last_n window over the full
        # context. Kept all-False (and statically unused) when disabled.
        presence = jnp.zeros((1, cfg.vocab_size), dtype=bool)
        if use_rp:
            presence = presence.at[0, jnp.asarray(prompt_ids)].set(True)

        t0 = time.monotonic()
        logits, k_cache, v_cache = self._run_prefill(
            request.model, prompt_ids, cache_len
        )
        rng = jax.random.PRNGKey(request.seed)
        rng, sub = jax.random.split(rng)
        first = sample_token(
            logits,
            sub,
            jnp.float32(request.temperature),
            request.top_k,
            jnp.float32(request.top_p) if use_top_p else None,
            presence if use_rp else None,
            jnp.float32(request.repeat_penalty) if use_rp else None,
        )
        if use_rp:
            presence = presence.at[jnp.arange(1), first].set(True)
        jax.block_until_ready(first)
        t1 = time.monotonic()
        if _obs_enabled():
            _PREFILL_H.observe(t1 - t0)
            _TRACER.add_span(
                "prefill", t0, t1,
                attrs={"model": request.model, "prompt_tokens": s_real},
            )
        return {
            "tf": tf,
            "tok": tok,
            "s_real": s_real,
            "g_bucket": g_bucket,
            "first": first,
            "rng": rng,
            "k_cache": k_cache,
            "v_cache": v_cache,
            "presence": presence,
            "use_top_p": use_top_p,
            "use_rp": use_rp,
            "t0": t0,
            "t1": t1,
        }

    def _batch_states(
        self,
        requests: "list[GenerationRequest]",
        all_prompt_ids: "list[list[int]]",
        cache_lens: "list[int]",
        group_refs: bool = False,
    ) -> "list[Dict[str, Any]]":
        """Per-row decode states with GROUPED prefill (VERDICT round-4
        missing #3: the server's continuous batching decoded in lockstep
        but prefilled sequentially — at 128 rows, 128 one-at-a-time
        dispatches stood behind a 1.3 s decode; Ollama, the backend being
        replaced, batches admission prefill).

        Rows whose prompts are single-chunk, share a prompt bucket AND a
        cache length — and have no prefix-cache hit — prefill together as
        ONE padded ``[G, bucket]`` forward into a shared cache, then
        sample their first tokens with the same per-row rng machinery the
        batched decode loop uses (``sample_token_per_row``), so each
        row's stream stays bit-identical to a solo :meth:`generate`.
        Remaining rows (multi-chunk prompts, prefix hits) take the solo
        :meth:`_start` path unchanged. Grouped rows share the group's
        prefill wall-clock as their ``prefill_s`` — the same convention
        ``decode_s`` already uses for the shared batch window. Grouped
        prefills do not populate the prompt-prefix cache (per-row slices
        of the shared cache would pin HBM per row; the solo path still
        stores).

        ``group_refs=True`` (the contiguous one-shot batch): grouped
        rows carry a shared ``st["group"]`` dict (the group's whole k/v
        caches, firsts,
        presence and rng arrays) plus their index ``st["gi"]``, and the
        per-row ``first``/``k_cache``/``v_cache``/``presence``/``rng``
        slices are NOT created — each slice is a separate host→device
        dispatch (what they cost against their device time: not
        measured on the chip).
        The caller assembles rows with per-group gathers instead."""
        model = requests[0].model
        self.load_model(model)
        tf = self._models[model]
        cfg = tf.cfg
        tok = self._tokenizer_for(model)

        states: "list[Optional[Dict[str, Any]]]" = [None] * len(requests)
        groups: "Dict[Tuple[int, int], list[int]]" = {}
        for i, ids in enumerate(all_prompt_ids):
            if not ids:
                # preserve the solo path's clean empty-prompt failure
                states[i] = self._start(
                    requests[i], cache_len=cache_lens[i], prompt_ids=ids
                )
                continue
            chunks = _prompt_chunks(len(ids))
            # probe the prefix cache only where grouping would consume the
            # answer (single-chunk rows): multi-chunk rows go solo anyway,
            # and _run_prefill repeats the scan for hit rows — probing
            # here too would double the scan and the LRU refresh per row
            hit = (
                self._find_prefix(model, ids)
                if len(chunks) == 1 and self._prefix_enabled
                else None
            )
            if len(chunks) == 1 and hit is None:
                key = (chunks[0][1], cache_lens[i])
                groups.setdefault(key, []).append(i)
            else:
                states[i] = self._start(
                    requests[i], cache_len=cache_lens[i], prompt_ids=ids
                )
        from ..ops.sampling import sample_token_per_row

        for (bucket, cache_len), idxs in groups.items():
            if len(idxs) == 1:  # no grouping win; identical solo semantics
                i = idxs[0]
                states[i] = self._start(
                    requests[i],
                    cache_len=cache_len,
                    prompt_ids=all_prompt_ids[i],
                )
                continue
            t0 = time.monotonic()
            g = len(idxs)
            gb = _bucket(g, BATCH_BUCKETS)
            pad = gb - g
            row_ids = [all_prompt_ids[i] for i in idxs]
            row_ids += [row_ids[0]] * pad
            row_reqs = [requests[i] for i in idxs]
            row_reqs += [row_reqs[0]] * pad
            tokens = jnp.asarray(
                [ids + [tok.pad_id] * (bucket - len(ids)) for ids in row_ids],
                dtype=jnp.int32,
            )
            last_index = jnp.asarray([len(ids) - 1 for ids in row_ids])
            k_cache, v_cache = tf.init_cache(gb, cache_len, dtype=self.dtype)
            k_cache, v_cache = self._place_cache(k_cache, v_cache, cfg)
            prefill = self._prefill_fn(model, bucket, cache_len)
            logits, k_cache, v_cache = prefill(
                tf.params, tokens, jnp.int32(0), last_index, k_cache, v_cache
            )
            # first-token sampling, per-row streams exactly as _start:
            # split each row's PRNGKey(seed) once, sample with the sub key
            rngs0 = jnp.stack(
                [jax.random.PRNGKey(r.seed) for r in row_reqs]
            )
            split = jax.vmap(jax.random.split)(rngs0)
            rngs, subs = split[:, 0], split[:, 1]
            use_top_p = any(r.top_p < 1.0 for r in row_reqs)
            use_rp = any(r.repeat_penalty != 1.0 for r in row_reqs)
            import numpy as np

            pres_np = np.zeros((gb, cfg.vocab_size), dtype=bool)
            if use_rp:
                for gi, (r, ids) in enumerate(zip(row_reqs, row_ids)):
                    if r.repeat_penalty != 1.0:
                        pres_np[gi, ids] = True
            presence = jnp.asarray(pres_np)
            temps = jnp.asarray(
                [r.temperature for r in row_reqs], dtype=jnp.float32
            )
            # same sentinel convention as the batched decode loop: rows
            # with nucleus filtering off get 2.0 so the any-row-enabled
            # filter is a provable identity for them
            top_ps = jnp.asarray(
                [r.top_p if r.top_p < 1.0 else 2.0 for r in row_reqs],
                dtype=jnp.float32,
            )
            rps = jnp.asarray(
                [r.repeat_penalty for r in row_reqs], dtype=jnp.float32
            )
            firsts = sample_token_per_row(
                logits,
                subs,
                temps,
                row_reqs[0].top_k,
                top_ps if use_top_p else None,
                presence if use_rp else None,
                rps if use_rp else None,
            )
            if use_rp:
                presence = presence.at[jnp.arange(gb), firsts].set(True)
            jax.block_until_ready(firsts)
            t1 = time.monotonic()
            if _obs_enabled():
                _PREFILL_H.observe(t1 - t0)
                _TRACER.add_span(
                    "prefill", t0, t1,
                    attrs={"model": model, "rows": g, "bucket": bucket},
                )
            shared = {
                "k": k_cache,
                "v": v_cache,
                "first": firsts,
                "presence": presence,
                "rng": rngs,
            }
            for gi, i in enumerate(idxs):
                r = requests[i]
                states[i] = {
                    "tf": tf,
                    "tok": tok,
                    "s_real": len(all_prompt_ids[i]),
                    "g_bucket": _bucket(r.max_new_tokens, GEN_BUCKETS),
                    "use_top_p": r.top_p < 1.0,
                    "use_rp": r.repeat_penalty != 1.0,
                    "t0": t0,
                    "t1": t1,
                }
                if group_refs:
                    states[i]["group"] = shared
                    states[i]["gi"] = gi
                else:
                    states[i].update(
                        first=firsts[gi : gi + 1],
                        rng=rngs[gi],
                        # rows on axis 1 of every leaf (a state-space
                        # model's k_cache is a record of several)
                        k_cache=jax.tree_util.tree_map(
                            lambda a, gi=gi: a[:, gi : gi + 1], k_cache
                        ),
                        v_cache=v_cache[:, gi : gi + 1],
                        presence=presence[gi : gi + 1],
                    )
        return states  # type: ignore[return-value]

    @staticmethod
    def _row_field_specs(
        states: "list[Dict[str, Any]]",
    ) -> "list[Tuple[str, str, int, Callable]]":
        """The (first / presence / rng) :meth:`_assemble_rows` specs
        shared by both batch paths — defined once so the paged and
        contiguous row assemblies cannot drift; the contiguous path
        extends the list with its cache fields."""
        return [
            (
                "first", "first", 0,
                lambda rows: jnp.concatenate(
                    [states[r]["first"] for r in rows]
                ),
            ),
            (
                "presence", "presence", 0,
                lambda rows: jnp.concatenate(
                    [states[r]["presence"] for r in rows], axis=0
                ),
            ),
            (
                "rng", "rng", 0,
                lambda rows: jnp.stack(
                    [states[r]["rng"] for r in rows]
                ),
            ),
        ]

    def _assemble_rows(
        self,
        states: "list[Dict[str, Any]]",
        b_bucket: int,
        fields: "list[Tuple[str, str, int, Callable]]",
    ) -> "Dict[str, Any]":
        """Assemble per-row batch arrays from grouped-prefill refs: ONE
        gather per group per field plus one permutation take, instead of
        per-row slices — each slice is a separate host→device dispatch,
        and those dispatches drain inside the decode wall-clock window
        (what they cost: not measured on the chip).

        ``fields`` entries are ``(out_name, group_field_key, axis,
        solo_builder)``: the group arrays gather along ``axis``; rows
        from solo-prefilled states (no ``st["group"]``) come from
        ``solo_builder(solo_row_indices)``. Padding rows (`b_bucket` −
        len(states)) replicate row 0, which enters decode pre-done.

        Returns the assembled fields plus ``_groups`` / ``_group_idx``
        (the paged chunk loop reuses them). Callers pop ``st["group"]``
        when done with the group arrays so the bucket-padded prefill
        caches free before the decode loop allocates."""
        import numpy as np

        n = len(states)
        groups: "Dict[int, Tuple[Dict[str, Any], list[int]]]" = {}
        for r, st in enumerate(states):
            if "group" in st:
                groups.setdefault(
                    id(st["group"]), (st["group"], [])
                )[1].append(r)
        group_idx = {
            gid: jnp.asarray(
                [states[r]["gi"] for r in members], jnp.int32
            )
            for gid, (_, members) in groups.items()
        }
        solo_rows = [r for r, st in enumerate(states) if "group" not in st]
        perm = np.zeros(b_bucket, dtype=np.int32)
        pos = 0
        for _, members in groups.values():
            for j, r in enumerate(members):
                perm[r] = pos + j
            pos += len(members)
        for j, r in enumerate(solo_rows):
            perm[r] = pos + j
        perm[n:] = perm[0]  # pad rows replicate row 0
        perm_j = jnp.asarray(perm)

        gi_lists = {
            gid: [states[r]["gi"] for r in members]
            for gid, (_, members) in groups.items()
        }
        perm_identity = bool(np.array_equal(perm, np.arange(b_bucket)))

        out: "Dict[str, Any]" = {
            "_groups": groups,
            "_group_idx": group_idx,
        }
        for name, key, axis, solo_builder in fields:
            parts = []
            for gid, (shared, _) in groups.items():
                arr = shared[key]
                # identity gather (members are the whole group in order,
                # the common all-rows-one-group case) → no device copy
                if gi_lists[gid] == list(range(arr.shape[axis])):
                    parts.append(arr)
                else:
                    parts.append(jnp.take(arr, group_idx[gid], axis=axis))
            if solo_rows:
                parts.append(solo_builder(solo_rows))
            cat = (
                parts[0]
                if len(parts) == 1
                else jnp.concatenate(parts, axis=axis)
            )
            out[name] = (
                cat
                if perm_identity and cat.shape[axis] == b_bucket
                else jnp.take(cat, perm_j, axis=axis)
            )
        return out

    # -- observability --------------------------------------------------------
    def _obs_labels(self) -> Dict[str, str]:
        """The attention-path labels of every step this engine runs."""
        return {
            "path": "paged" if self.paged_kv else "contiguous",
            "kv": "int8" if self.kv_quantize else "bf16",
        }

    def _observe_decode_window(
        self, t1: float, t2: float, tokens: int, steps: int, rows: int = 1
    ) -> None:
        """One decode window into the registry + a span (parented under
        the serving request's root when the scheduler attached one) + a
        flight-recorder event linking back to the request's span tree."""
        labels = self._obs_labels()
        _DECODE_H.observe(t2 - t1)
        _TOKENS_C.labels(**labels).inc(tokens)
        _STEPS_C.labels(**labels).inc(steps)
        if t2 > t1 and tokens:
            _TOKS_PER_S_G.labels(**labels).set(tokens / (t2 - t1))
        _TRACER.add_span(
            "decode", t1, t2,
            attrs={"tokens": tokens, "rows": rows, **labels},
        )
        from ..obs.flight import EV_DECODE_WINDOW, FLIGHT, trace_attrs

        FLIGHT.emit(
            EV_DECODE_WINDOW,
            **trace_attrs(_TRACER.current()),
            tokens=tokens,
            steps=steps,
            rows=rows,
            dur_s=round(t2 - t1, 6),
            **labels,
        )

    def _observe_result(self, result: GenerationResult, st: Dict[str, Any], t2: float) -> None:
        """Solo-window telemetry + live energy attribution: the run-table
        energy model evaluated on this result (nominal + the coefficient
        box), attached as ``extras["energy_model"]`` and recorded in the
        ``llm_request_*`` families. Telemetry must never fail a request."""
        if not _obs_enabled():
            return
        try:
            self._observe_decode_window(
                st["t1"], t2, result.generated_tokens, result.generated_tokens
            )
            from ..obs import energy as obs_energy

            model = result.request.model
            tf = self._models.get(model)
            if tf is None:
                return
            est = obs_energy.attribute_result(
                tf.cfg,
                result,
                quantize=self._quant_mode(model),
                kv_quantize=self.kv_quantize,
                n_chips=max(1, getattr(self, "n_devices", 1)),
                chip=self.chip,
            )
            if est is not None:
                result.extras = {**(result.extras or {}), "energy_model": est}
                obs_energy.observe_estimate(est)
                # live figure for router probes (ISSUE 13): LocalReplica
                # reads this attribute so least-joules routing works on
                # real engines without a loopback /metrics scrape; the
                # per-model split feeds the multi-model fleet's
                # cheapest-joules policy (ISSUE 15)
                if est.get("J_per_token") is not None:
                    self.last_joules_per_token = est["J_per_token"]
                    self.last_joules_per_token_by_model[model] = est[
                        "J_per_token"
                    ]
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _observe_batch_window(
        self, model: str, results: "list[GenerationResult]", t1: float, t2: float
    ) -> None:
        """Shared-window telemetry for one batched decode: bills the
        weight stream ONCE per step for the whole window (per-row solo
        estimates would multiply-count it — the decode_s convention) and
        attributes each row its token share of the window's Joules."""
        if not _obs_enabled() or not results:
            return
        try:
            tokens = sum(r.generated_tokens for r in results)
            steps = max(r.generated_tokens for r in results)
            self._observe_decode_window(
                t1, t2, tokens, steps, rows=len(results)
            )
            from ..obs import energy as obs_energy

            tf = self._models.get(model)
            if tf is None or not tokens:
                return
            stats = obs_energy.batch_window_stats(
                tf.cfg,
                results,
                quantize=self._quant_mode(model),
                kv_quantize=self.kv_quantize,
                duration_s=t2 - t1,
            )
            est = (
                obs_energy.estimate_from_stats(
                    stats,
                    n_chips=max(1, getattr(self, "n_devices", 1)),
                    chip=self.chip,
                )
                if stats
                else None
            )
            if est is None:
                return
            obs_energy.observe_estimate(est)
            if est.get("J_per_token") is not None:
                self.last_joules_per_token = est["J_per_token"]
                self.last_joules_per_token_by_model[model] = est[
                    "J_per_token"
                ]
            for r in results:
                if not r.generated_tokens:
                    continue
                share = r.generated_tokens / tokens
                r.extras = {
                    **(r.extras or {}),
                    "energy_model": {
                        "J": round(est["J"] * share, 4),
                        "J_low": round(est["J_low"] * share, 4),
                        "J_high": round(est["J_high"] * share, 4),
                        "J_per_token": est["J_per_token"],
                        "J_per_token_low": est["J_per_token_low"],
                        "J_per_token_high": est["J_per_token_high"],
                        "power_model_W": est["power_model_W"],
                        "chip": est["chip"],
                        "window": "shared",  # token-share of the batch
                    },
                }
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _slice_energy(
        self,
        model: str,
        cfg,
        pairs,
        duration_s: float,
        steps: int,
    ) -> "Optional[Dict[str, Any]]":
        """Energy-model estimate for ONE continuous-decode slice (or one
        join-prefill chunk) — ``slice_window_stats`` evaluated with this
        engine's quantize modes and chip count (ISSUE 20). The stepped
        sessions split the returned J/J_low/J_high across their rows by
        token share. None when the model can't price it; never raises
        past the callers' telemetry guards."""
        from ..obs import energy as obs_energy

        stats = obs_energy.slice_window_stats(
            cfg,
            pairs,
            duration_s,
            steps,
            quantize=self._quant_mode(model),
            kv_quantize=self.kv_quantize,
        )
        if stats is None:
            return None
        return obs_energy.estimate_from_stats(
            stats,
            n_chips=max(1, getattr(self, "n_devices", 1)),
            chip=self.chip,
        )

    def _finish(
        self,
        request: GenerationRequest,
        generated: "list[int]",
        st: Dict[str, Any],
        t2: float,
    ) -> GenerationResult:
        eos = st["tok"].eos_id
        if request.stop_at_eos and eos in generated:
            generated = generated[: generated.index(eos)]
        text = st["tok"].decode(generated)
        if request.stop:
            generated, text = _apply_stop(generated, text, st["tok"], request.stop)
        result = GenerationResult(
            request=request,
            tokens=generated,
            text=text,
            prompt_tokens=st["s_real"],
            generated_tokens=len(generated),
            prefill_s=st["t1"] - st["t0"],
            decode_s=t2 - st["t1"],
            total_s=t2 - st["t0"],
        )
        self._observe_result(result, st, t2)
        return result

    def _resolve_spec(self, model: str):
        """The :class:`~.speculative.DraftSpec` that applies to
        ``model``: an exact entry wins, else the ``"default"`` entry
        (the draft-only CLI form). A model never drafts for itself via
        the default — that would pay k+1 forwards of the SAME weights
        per round for zero amortization (the ngram source has no draft
        model, so the rule never blocks it)."""
        spec = self.speculative.get(model)
        if spec is None:
            spec = self.speculative.get("default")
            if spec is not None and spec.draft == model:
                return None
        return spec

    def _spec_eligible(self, request: GenerationRequest) -> bool:
        """Speculation eligibility per request (ISSUE 16): greedy rows
        verify by argmax match (bit-parity), sampled rows by rejection
        resampling — any temperature up to ``spec_temperature_max``
        qualifies. The presence penalty stays excluded: it perturbs the
        modified distribution per EMITTED token, which the k-wide
        proposal step cannot replicate mid-round."""
        return (
            request.repeat_penalty == 1.0
            and (
                request.temperature == 0.0
                or request.temperature <= self.spec_temperature_max
            )
        )

    # -- per-source acceptance memory (ISSUE 16) ----------------------------
    @staticmethod
    def _spec_source_key(source: str, draft: "Optional[str]") -> str:
        return f"{source}:{draft or ''}"

    def _spec_source_feedback(
        self, source: str, draft: "Optional[str]", acceptance: float
    ) -> None:
        """Record one session's fallback acceptance under its source
        key (bounded window — only the recent past should gate)."""
        window = self._spec_source_health.setdefault(
            self._spec_source_key(source, draft), []
        )
        window.append(float(acceptance))
        del window[:-8]

    def _spec_source_clear(
        self, source: str, draft: "Optional[str]"
    ) -> None:
        """A session speculated to healthy completion: forget the
        source's fallback history so it re-arms immediately."""
        self._spec_source_health.pop(
            self._spec_source_key(source, draft), None
        )

    def _spec_source_blocked(
        self, source: str, draft: "Optional[str]", floor: float
    ) -> bool:
        """Whether new sessions should skip arming this source: ≥2
        recent fallbacks whose mean acceptance sits under the floor.
        Consulting pops the OLDEST entry, so a blocked source decays
        back to armed after a few skipped sessions — a cheap re-probe
        rather than a permanent ban. Keyed per source (ngram collapse
        on non-repetitive text must not gate model-draft sessions)."""
        if floor <= 0.0:
            return False
        window = self._spec_source_health.get(
            self._spec_source_key(source, draft)
        )
        if window is None or len(window) < 2:
            return False
        blocked = sum(window) / len(window) < floor
        if blocked:
            window.pop(0)
        return blocked

    def generate(self, request: GenerationRequest) -> GenerationResult:
        if request.stop:
            # Stop strings can only be matched on the host, so decode in
            # chunks via the streaming machinery, which exits within one
            # chunk of the hit — a monolithic decode would burn (and
            # *measure*) the full token budget for output that gets cut,
            # corrupting tokens/s and energy-per-token.
            for chunk in self.generate_stream(request):
                if chunk.done:
                    return chunk.result
            raise RuntimeError("stream ended without a final chunk")
        spec = self._resolve_spec(request.model)
        if spec is not None and self._spec_eligible(request):
            # Greedy rows get the same tokens as plain greedy decode,
            # just faster (the accepted tokens ARE the greedy tokens);
            # sampled rows get exactly target-distributed tokens via
            # rejection resampling (ISSUE 16). Requests whose
            # speculative cache margin wouldn't fit max_seq_len serve
            # plain — configuring a draft must never reject a request.
            self.load_model(request.model)
            cfg = self._models[request.model].cfg
            ids = self._tokenizer_for(request.model).encode(request.prompt)
            s_b = _prompt_alloc(len(ids))
            g_b = _bucket(request.max_new_tokens, GEN_BUCKETS)
            if s_b + g_b + _spec_margin(spec.k) <= cfg.max_seq_len:
                return self.generate_speculative(
                    request, spec.draft, spec.k, prompt_ids=ids,
                    source=spec.source,
                )
            return self._generate_plain(request, prompt_ids=ids)
        return self._generate_plain(request)

    def _generate_plain(
        self,
        request: GenerationRequest,
        prompt_ids: "Optional[list[int]]" = None,
    ) -> GenerationResult:
        """The non-speculative monolithic decode — also the fallback when a
        configured draft can't be co-resident with its target (a draft must
        never make a request fail that plain decoding would serve)."""
        st = self._start(request, prompt_ids=prompt_ids)
        st = self._maybe_quantize_cache(st)
        decode = self._decode_fn(
            request.model,
            st["g_bucket"],
            request.top_k,
            st["use_top_p"],
            st["use_rp"],
        )
        out, n_done, _, _, _, _ = decode(
            st["tf"].params,
            st["first"],
            jnp.int32(st["s_real"]),
            st["k_cache"],
            st["v_cache"],
            jnp.float32(request.temperature),
            st["rng"],
            jnp.int32(request.max_new_tokens - 1),  # first token already sampled
            jnp.float32(request.top_p),
            jnp.float32(request.repeat_penalty),
            st["presence"],
        )
        out = jax.block_until_ready(out)
        t2 = time.monotonic()

        # ONE device→host transfer for the whole token block, not a
        # per-element int(t) loop issuing one blocking device read per
        # token.
        generated = [int(st["first"][0])] + _to_host_list(
            out[0][: int(n_done)]
        )
        return self._finish(request, generated, st, t2)

    # -- speculative generation -----------------------------------------------
    def generate_speculative(
        self,
        request: GenerationRequest,
        draft_model: "Optional[str]" = None,
        k: int = 4,
        prompt_ids: "Optional[list[int]]" = None,
        source: str = "model",
    ) -> GenerationResult:
        """Decode via draft-and-verify (engine/speculative.py): the
        draft source proposes ``k`` tokens per round, the target
        verifies them in one forward. Greedy requests produce tokens
        bit-identical to plain greedy :meth:`generate`; sampled
        requests (ISSUE 16) produce exactly target-distributed tokens
        via rejection resampling. ``result.extras`` reports
        rounds/accepted.

        A model draft must share the target's vocabulary (same
        tokenizer); the KV caches carry a ``2k+2``-slot margin beyond
        the usual buckets, so requests near ``max_seq_len`` may need a
        smaller budget. ``source`` picks the draft lane: ``"model"`` /
        ``"cross"`` need ``draft_model``, ``"ngram"`` drafts from the
        request's own prompt+generated history (zero extra weights).

        Greedy model/cross requests keep the monolithic solo loop
        (``build_spec_fn`` — the whole budget in one compiled call);
        everything else (any sampled request, every ngram request)
        drains a one-row stepped session so the rejection-resampling
        lane and the n-gram matcher live in ONE compiled step — the
        temperature guard this method used to raise is now the sampled
        path.
        """
        if request.repeat_penalty != 1.0:
            raise ValueError(
                "speculative decoding requires repeat_penalty=1 (the "
                "presence penalty perturbs the modified distribution "
                "per emitted token, which a k-wide proposal step "
                "cannot replicate)"
            )
        if request.temperature != 0.0 or source == "ngram":
            from .speculative import DraftSpec

            override = DraftSpec(
                source, None if source == "ngram" else draft_model, k
            )
            result = self._drain_session(
                self.decode_open([request], spec_override=override)
            )[0]
            spec_x = (result.extras or {}).get("spec")
            if spec_x is not None:
                # legacy flat keys, for wire parity with the greedy
                # solo path's extras shape
                result.extras.update(
                    spec_rounds=spec_x["rounds"],
                    spec_accepted=spec_x["accepted"],
                    draft_model=spec_x["draft_model"],
                    k=spec_x["k"],
                )
            return result
        model = request.model
        self.load_model(model)
        self.load_model(draft_model)
        if model not in self._models:
            # The draft's load may have LRU-evicted the target; one retry.
            # Note the retry can itself evict the draft (the draft becomes
            # the oldest un-pinned resident) — that case falls through to
            # the co-residency check below.
            self.load_model(model)
        if model not in self._models or draft_model not in self._models:
            # The pair genuinely can't be co-resident under the allocation
            # budget: serve the request WITHOUT the draft rather than
            # failing it — plain greedy decode produces the same tokens.
            from ..runner import term

            term.log_warn(
                f"speculative decoding: {model} and {draft_model} cannot "
                "be co-resident under the device allocation budget; "
                "falling back to plain decode (raise "
                "TPU_ALLOC_BUDGET_BYTES or drop the draft to avoid this)"
            )
            return self._generate_plain(request, prompt_ids=prompt_ids)
        tcfg = self._models[model].cfg
        dcfg = self._models[draft_model].cfg
        if tcfg.vocab_size != dcfg.vocab_size:
            raise ValueError(
                f"draft {draft_model} vocab {dcfg.vocab_size} != target "
                f"{model} vocab {tcfg.vocab_size}"
            )

        tok = self._tokenizer_for(model)
        if prompt_ids is None:
            prompt_ids = tok.encode(request.prompt)
        s_real = len(prompt_ids)
        s_bucket = _prompt_alloc(s_real)
        g_bucket = _bucket(request.max_new_tokens, GEN_BUCKETS)
        cache_len = s_bucket + g_bucket + _spec_margin(k)

        # target prefill + first greedy token (shared path, margin cache);
        # under kv_quantize the TARGET decodes over the int8 cache — the
        # verify block's writes quantize per vector exactly like the
        # plain int8 decode step, so the accepted tokens are the int8
        # engine's own greedy stream (the draft cache below stays at the
        # engine dtype: it is tiny)
        st = self._maybe_quantize_cache(
            self._start(request, cache_len=cache_len, prompt_ids=prompt_ids)
        )

        # draft prefill over the same token ids
        dft = self._models[draft_model]
        _, dkc, dvc = self._run_prefill(draft_model, prompt_ids, cache_len)

        key = ("spec", model, draft_model, k, g_bucket)
        if key not in self._decode_cache:
            from .speculative import build_spec_fn

            # The verify step runs attention for only k+1 query rows — far
            # below the flash-prefill kernel's tile size; the XLA-fused jnp
            # path is the right tool there (prefill_attention=None). The
            # prompt prefill in _start still uses the flash kernel.
            self._decode_cache[key] = build_spec_fn(
                tcfg,
                dcfg,
                k,
                g_bucket,
                tok.eos_id,
                self.decode_attention,
                None,
            )
        spec = self._decode_cache[key]
        out, n_em, rounds, acc = spec(
            self._models[model].params,
            dft.params,
            st["first"],
            jnp.int32(s_real),
            st["k_cache"],
            st["v_cache"],
            dkc,
            dvc,
            jnp.int32(request.max_new_tokens - 1),
        )
        out = jax.block_until_ready(out)
        t2 = time.monotonic()

        take = min(int(n_em), request.max_new_tokens - 1)
        generated = [int(st["first"][0])] + _to_host_list(out[:take])
        result = self._finish(request, generated, st, t2)
        rounds, acc = int(rounds), int(acc)
        # merge, not replace — _finish may have attached energy extras.
        # The legacy flat keys stay for wire compatibility; the nested
        # "spec" block is the ISSUE-9 shape the stepped path also emits.
        result.extras = {
            **(result.extras or {}),
            "spec_rounds": rounds,
            "spec_accepted": acc,
            "draft_model": draft_model,
            "k": k,
            "spec": {
                "rounds": rounds,
                "accepted": acc,
                "drafted": rounds * k,
                "k": k,
                "draft_model": draft_model,
                "source": source,
            },
        }
        if _obs_enabled():
            try:
                from ..obs.metrics import observe_spec

                observe_spec(rounds, acc, rounds * k, source=source)
                from ..obs.flight import EV_SPEC_ROUND, FLIGHT, trace_of

                FLIGHT.emit(
                    EV_SPEC_ROUND,
                    trace=trace_of(_TRACER.current()),
                    model=request.model,
                    draft=draft_model,
                    source=source,
                    k=k,
                    rounds=rounds,
                    accepted=acc,
                    acceptance=(
                        round(acc / (rounds * k), 4) if rounds else None
                    ),
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        return result

    # -- batched generation ---------------------------------------------------
    def _batch_decode_fn(
        self,
        model: str,
        n_steps: int,
        top_k: int,
        use_top_p: bool,
        use_rp: bool,
    ) -> Callable:
        """Batched decode loop: per-row offsets, rng streams, sampling knobs
        and done-masks, so every row's token stream is bit-identical to a
        single-request :meth:`generate` with that row's request. One shared
        ``lax.while_loop`` amortises the HBM weight stream over all rows —
        the throughput win batching exists for."""
        key = ("batch", model, n_steps, top_k, use_top_p, use_rp)
        if key in self._decode_cache:
            return self._decode_cache[key]
        tf = self._models[model]
        cfg = tf.cfg
        # the attention matching the cache representation (int8 codes +
        # per-(row, head, position) scales under kv_quantize)
        decode_attention = self._decode_attention_for_cache(cfg)
        eos = self._tokenizer_for(model).eos_id

        from ..ops.sampling import sample_token_per_row

        @jax.jit
        def decode(
            params,
            first_tokens,  # [B]
            offsets,  # [B] — each row's next cache write position
            k_cache,
            v_cache,
            temperature,  # [B]
            rngs,  # [B] keys
            n_real,  # scalar: max steps this call
            top_p,  # [B]
            repeat_penalty,  # [B]
            presence,  # [B, vocab]
            done0,  # [B] — padding rows enter pre-done
        ):
            b = first_tokens.shape[0]

            def cond(carry):
                _, _, _, _, _, done, i, _, _, _ = carry
                return (i < n_real) & ~jnp.all(done)

            def body(carry):
                token, offs, kc, vc, rngs, done, i, out, pres, n_row = carry
                prev_done = done
                hidden, kc, vc = forward(
                    params, cfg, token[:, None], offs, kc, vc, decode_attention
                )
                logits = logits_for(params, cfg, hidden[:, 0])
                with jax.named_scope("sample"):
                    split = jax.vmap(jax.random.split)(rngs)
                    rngs, subs = split[:, 0], split[:, 1]
                nxt = sample_token_per_row(
                    logits,
                    subs,
                    temperature,
                    top_k,
                    top_p if use_top_p else None,
                    pres if use_rp else None,
                    repeat_penalty if use_rp else None,
                )
                nxt = jnp.where(done, jnp.int32(eos), nxt)
                done = done | (nxt == eos)
                if use_rp:
                    pres = pres.at[jnp.arange(b), nxt].set(True)
                out = out.at[:, i].set(nxt)
                # Rows still live at entry record this step; matches the
                # single-request loop's exit value of its step counter.
                n_row = jnp.where(prev_done, n_row, i + 1)
                return (
                    nxt, offs + 1, kc, vc, rngs, done, i + 1, out, pres, n_row
                )

            out0 = jnp.full((b, n_steps), eos, dtype=jnp.int32)
            init = (
                first_tokens,
                offsets,
                k_cache,
                v_cache,
                rngs,
                done0,
                jnp.int32(0),
                out0,
                presence,
                jnp.zeros((b,), dtype=jnp.int32),
            )
            *_, out_tokens, _, n_row = jax.lax.while_loop(cond, body, init)
            return out_tokens, n_row

        self._decode_cache[key] = decode
        return decode

    # -- stepped (iteration-level) decode --------------------------------------
    # -- stepped-carry SPMD hooks (engine/stepped.py sessions) ---------------
    def _stepped_carry_shardings(
        self, cfg: ModelConfig, carry, draft_cfg: Optional[ModelConfig] = None
    ):
        """Per-leaf NamedShardings for a stepped session carry, or None
        on the single-device engine (jit's default placement is already
        right there). The TP engine returns the
        ``parallel/sharding.py::stepped_carry_shardings`` pytree —
        KV payload sharded over heads when they divide the mesh,
        row-control state replicated. ``draft_cfg`` names the DRAFT
        model of a speculative session: its ``draft_k``/``draft_v``
        leaves shard by the draft's own head count (which may differ
        from the target's)."""
        return None

    def _dp_shards(self) -> int:
        """dp extent of the engine's mesh (ISSUE 19 tp×dp row sharding):
        1 on the single-device engine; the TP engine reports its mesh's
        ``dp`` axis so a stepped session can pre-partition its page pool
        into per-shard ranges matching the carry's row split."""
        return 1

    def _place_carry(
        self, cfg: ModelConfig, carry, draft_cfg: Optional[ModelConfig] = None
    ):
        """Explicitly place an assembled stepped carry on the device(s).
        Identity here; the TP engine device_puts every leaf with its
        carry sharding so the session starts (and stays) committed to
        the mesh placement the jitted slice step declares."""
        return carry

    def _stepped_jit(
        self,
        cfg: ModelConfig,
        carry,
        fn,
        draft_cfg: Optional[ModelConfig] = None,
    ) -> Callable:
        """jit one stepped slice step ``(params, carry, n_real) ->
        (out_tokens, n_row, carry)``. On accelerator backends the carry
        argument is DONATED — the slice's output carry aliases its input
        buffers, so a session's KV pool never holds 2× liveness across a
        step. The TP override adds explicit
        ``in_shardings``/``out_shardings`` from the carry's sharding
        pytree, making the compiled step a pure SPMD program that never
        bounces the carry through host memory."""
        return jax.jit(fn, **_stepped_donation())

    def _row_install_jit(
        self,
        cfg: ModelConfig,
        carry,
        fn,
        draft_cfg: Optional[ModelConfig] = None,
    ) -> Callable:
        """jit a session's row install ``(row, carry) -> carry``
        (engine/stepped.py ``_row_program``): the carry is argument 1
        and donated exactly as the slice step's is, so a joiner's pages
        are written into the pool where it lies. The TP override
        declares the carry's shardings on both sides."""
        return jax.jit(fn, **_stepped_donation())

    def _row_install_fn(
        self, model: str, carry, draft_model: Optional[str] = None
    ) -> Callable:
        """The jitted row install for carries shaped like ``carry``
        (engine/stepped.py ``_row_program`` under
        :meth:`_row_install_jit`). The body reads nothing but its
        arguments' structure, so the wrapper is cached by the carry's
        tree and leaf shapes: sessions that open at the same shapes
        share one wrapper and its executables, and a mesh's declared
        shardings always belong to the carry they were made from."""
        from .stepped import _row_program

        leaves, tree = jax.tree_util.tree_flatten(carry)
        key = (
            "row-install", model, draft_model, tree,
            tuple((leaf.shape, leaf.dtype) for leaf in leaves),
        )
        if key not in self._decode_cache:
            self._decode_cache[key] = self._row_install_jit(
                self._models[model].cfg,
                carry,
                _row_program,
                draft_cfg=(
                    self._models[draft_model].cfg
                    if draft_model is not None
                    else None
                ),
            )
        return self._decode_cache[key]

    def _stepped_compute_ctx(self):
        """Context the stepped session wraps device compute in
        (open/step/join chunks). Null here; the TP engine disables the
        Pallas kernels that have no partitioning rule inside it (the int4
        matmul, the grouped expert FFN) — the same GSPMD rule its
        generate paths already apply."""
        import contextlib

        return contextlib.nullcontext()

    def device_report(self) -> Dict[str, Any]:
        """What JAX is attached to — ``{"platform", "kind", "count"}`` —
        for ``GET /debug/state`` and the ``serve`` start-up line, plus
        this process's device memory counters where the backend reports
        them (``peak_bytes_in_use`` is how a client confirms a slice
        never held two pools)."""
        report = device_report()
        stats = [d.memory_stats() for d in jax.local_devices()]
        if all(stats):
            report["memory"] = [
                {
                    key: int(st[key])
                    for key in (
                        "bytes_in_use", "peak_bytes_in_use", "bytes_limit"
                    )
                    if key in st
                }
                for st in stats
            ]
        return report

    def mesh_info(self) -> Optional[Dict[str, Any]]:
        """Device-mesh description for debug/introspection surfaces
        (``GET /debug/state``): None on the single-device engine; the TP
        engine reports device count, axis sizes and platform."""
        return None

    def _batch_decode_step_fn(
        self,
        model: str,
        n_steps: int,
        top_k: int,
        use_top_p: bool,
        use_rp: bool,
        carry=None,
    ) -> Callable:
        """Stepped twin of :meth:`_batch_decode_fn` for iteration-level
        scheduling: runs AT MOST ``n_real`` (≤ the compiled ``n_steps``
        slice) decode steps and returns the FULL loop carry, so the
        caller (engine/stepped.py) regains control between slices to
        retire finished rows and admit queued requests into the freed
        slots. Two deltas vs the monolithic loop, both parity-safe: a
        per-row ``remaining`` budget folds into the done mask (the
        tokens it cuts are exactly the post-budget ones the monolithic
        path samples and then discards at ``take = min(n_row,
        budget)``), and done rows freeze their offsets (a retired slot
        must not walk its write position across the cache while it
        idles; a live row's offsets advance identically).

        The carry travels as ONE pytree (`{"tokens", "offsets",
        "prompt_lens", "k_cache", "v_cache", "rngs", "presence",
        "done", "remaining", "temps", "top_ps", "rps"}`), jitted via
        :meth:`_stepped_jit`: the carry argument is donated on
        accelerator backends, and on a
        sharded engine every leaf carries an explicit NamedSharding —
        sampling-knob leaves the loop doesn't advance pass through
        unchanged (input→output aliased), which is what lets the host
        keep them in the same pytree without paying a copy per slice.
        ``carry`` here is a structure/placement EXAMPLE for the jit
        wrapper; the compiled fn is cached per (model, slice, knobs)."""
        key = ("batch-step", model, n_steps, top_k, use_top_p, use_rp)
        if key in self._decode_cache:
            return self._decode_cache[key]
        tf = self._models[model]
        cfg = tf.cfg
        decode_attention = self._decode_attention_for_cache(cfg)
        eos = self._tokenizer_for(model).eos_id

        from ..ops.sampling import sample_token_per_row

        def decode(params, carry, n_real):
            first_tokens = carry["tokens"]  # [B] — each row's last token
            offsets = carry["offsets"]  # [B]
            k_cache, v_cache = carry["k_cache"], carry["v_cache"]
            temperature = carry["temps"]  # [B]
            rngs = carry["rngs"]  # [B] keys
            remaining = carry["remaining"]  # [B] budget BEFORE this slice
            top_p = carry["top_ps"]  # [B]
            repeat_penalty = carry["rps"]  # [B]
            presence = carry["presence"]  # [B, vocab]
            done0 = carry["done"]  # [B] — retired/free slots stay done
            b = first_tokens.shape[0]

            def cond(carry):
                _, _, _, _, _, done, i, _, _, _ = carry
                return (i < n_real) & ~jnp.all(done)

            def body(carry):
                token, offs, kc, vc, rngs, done, i, out, pres, n_row = carry
                prev_done = done
                hidden, kc, vc = forward(
                    params, cfg, token[:, None], offs, kc, vc, decode_attention
                )
                logits = logits_for(params, cfg, hidden[:, 0])
                with jax.named_scope("sample"):
                    split = jax.vmap(jax.random.split)(rngs)
                    rngs, subs = split[:, 0], split[:, 1]
                nxt = sample_token_per_row(
                    logits,
                    subs,
                    temperature,
                    top_k,
                    top_p if use_top_p else None,
                    pres if use_rp else None,
                    repeat_penalty if use_rp else None,
                )
                with jax.named_scope("carry"):
                    nxt = jnp.where(done, jnp.int32(eos), nxt)
                    done = done | (nxt == eos) | (i + 1 >= remaining)
                    if use_rp:
                        pres = pres.at[jnp.arange(b), nxt].set(True)
                    out = out.at[:, i].set(nxt)
                    n_row = jnp.where(prev_done, n_row, i + 1)
                    offs = jnp.where(done, offs, offs + 1)
                return (
                    nxt, offs, kc, vc, rngs, done, i + 1, out, pres, n_row
                )

            out0 = jnp.full((b, n_steps), eos, dtype=jnp.int32)
            init = (
                first_tokens,
                offsets,
                k_cache,
                v_cache,
                rngs,
                done0,
                jnp.int32(0),
                out0,
                presence,
                jnp.zeros((b,), dtype=jnp.int32),
            )
            (
                token, offs, kc, vc, rngs_out, done, _, out_tokens,
                pres_out, n_row,
            ) = jax.lax.while_loop(cond, body, init)
            new_carry = dict(
                carry,
                tokens=token,
                offsets=offs,
                k_cache=kc,
                v_cache=vc,
                rngs=rngs_out,
                presence=pres_out,
                done=done,
                remaining=remaining - n_row,
            )
            return out_tokens, n_row, new_carry

        decode = self._stepped_jit(cfg, carry, decode)
        self._decode_cache[key] = decode
        return decode

    def _paged_batch_decode_step_fn(
        self,
        model: str,
        n_steps: int,
        top_k: int,
        use_top_p: bool,
        use_rp: bool,
        stacked: bool,
        quantized: bool,
        shared_pages: bool,
        carry=None,
    ) -> Callable:
        """The ONE decode loop over a page pool, a slice at a time:
        whoever decodes paged rows (the continuous scheduler, a paged
        :meth:`generate_batch`) steps a session through it. Forced by
        resumability: the pool/table/side-caches travel in the carry,
        not in closures (a mid-flight join writes new prefill pages
        into the pool between slices, so the compiled fn must read the
        caller's current arrays), ``prompt_lens`` is an explicit carry
        leaf (at slice ≥ 2 the entry offsets are no longer the prompt
        lengths), and the full carry returns. A row is done at EOS or
        when its own ``remaining`` budget is spent; after that it
        re-writes one frozen slot and consumes no fresh pages.

        Carry pytree (paged): the contiguous leaves minus the batch
        cache, plus ``{"pool_k", "pool_v", "table", "side_k",
        "side_v"}``. In stacked mode the pool passes through unchanged
        (read-only per slice — generated tokens land in the side
        caches) and the side caches thread the loop; legacy mode
        threads the pool and passes the scalar side sentinel through.
        Same jit discipline as the contiguous twin: carry donated on
        accelerator backends,
        explicit shardings on a mesh (heads-sharded pool/side payload,
        replicated table/row-control — see
        ``parallel/sharding.py::stepped_carry_shardings``).

        A model with an expert layer carries one more leaf,
        ``moe_counts`` (int32 ``[5]``): the slice's sums, over its steps
        and layers, of token-expert pairs on held, identity and absent
        experts, of held experts touched and of blocks (models/transformer.py
        ``_moe_parts``), counted over the rows live at each step; rows
        that are done route nowhere. The session fetches it with the
        slice's tokens. A model with state-space layers carries its
        recurrent state, ``ssm`` (models/ssm.py: ``s [Ls,B,H,P,N]``
        float32 and ``conv``), the whole row bucket's: every step reads
        and writes it where it lies, the rows that are not done (the
        step's ``token_mask``) alone where the step's kernel fits
        (``ssm_step_impl``); a done row's state stands still either way.
        Other models' programs carry nothing new.

        ``shared_pages`` says a pool page may sit in several rows'
        tables (the session has a prefix store). Where it may not, and
        the step's attention reads the pool in place
        (:meth:`_paged_decode_impl`: ``"xla-pool"`` or
        ``"pallas-pool"``), the slice builds the table's inverse once
        (``pool_page_owners``; under ``"pallas-pool"`` with the list of
        the held pages, the kernel's grid: ``held_page_list``) and every
        layer of every step
        reads the pool through it."""
        decode_attention = self._paged_decode_attention(
            self._models[model].cfg
        )
        key = (
            "paged-step", model, n_steps, top_k, use_top_p, use_rp,
            stacked, quantized, shared_pages,
        )
        if key in self._decode_cache:
            return self._decode_cache[key]
        tf = self._models[model]
        cfg = tf.cfg
        eos = self._tokenizer_for(model).eos_id

        from ..ops.pallas_paged_attention import (
            held_page_list,
            pool_page_owners,
        )
        from ..ops.sampling import sample_token_per_row

        def decode(params, carry, n_real):
            first_tokens = carry["tokens"]  # [B]
            offsets = carry["offsets"]  # [B]
            prompt_lens = carry["prompt_lens"]  # [B] static between joins
            pool_k = carry["pool_k"]  # [L, P, Hkv, page, D] — or {"q","s"}
            pool_v = carry["pool_v"]
            table = carry["table"]  # [B, Jmax] int32
            side_k = carry["side_k"]  # stacked: [L,B,Hkv,Tgen,D]; else 0
            side_v = carry["side_v"]
            temperature = carry["temps"]
            rngs = carry["rngs"]
            remaining = carry["remaining"]  # [B]
            top_p = carry["top_ps"]
            repeat_penalty = carry["rps"]
            presence = carry["presence"]
            done0 = carry["done"]
            b = first_tokens.shape[0]
            l = (pool_k["q"] if quantized else pool_k).shape[0]
            table_c = (
                table if stacked else jnp.broadcast_to(
                    table, (l,) + table.shape
                )
            )
            pool_named = {}
            impl = stacked and self._paged_decode_impl(
                cfg, *table.shape, shared_pages, pool_k
            )
            if impl in ("xla-pool", "pallas-pool"):
                # table and prompt lengths stand still for the slice
                n_pool, _, page = (
                    pool_k["q"] if quantized else pool_k
                ).shape[1:4]
                with jax.named_scope("attn.kv_gather"):
                    owners = pool_page_owners(
                        table, prompt_lens, n_pool, page
                    )
                    pool_named["owners"] = (
                        held_page_list(owners)
                        if impl == "pallas-pool"
                        else owners
                    )

            def cond(carry):
                done, i = carry[5], carry[6]
                return (i < n_real) & ~jnp.all(done)

            def body(carry):
                (
                    token, offs, pk, pv, rngs, done, i, out, pres, n_row,
                    *tail,
                ) = carry
                moe_n, ssm_n = tail[:n_moe], tail[n_moe:]
                prev_done = done
                if stacked:
                    kc = {
                        "pool": pool_k, "table": table_c, "side": pk,
                        "write_pos": offs - prompt_lens,
                        "prompt_lens": prompt_lens, **pool_named,
                    }
                    vc = {
                        "pool": pool_v, "table": table_c, "side": pv,
                        "write_pos": offs - prompt_lens,
                        "prompt_lens": prompt_lens,
                    }
                else:
                    kc = {"pool": pk, "table": table_c}
                    vc = {"pool": pv, "table": table_c}
                stats: Dict[str, Any] = {}
                if ssm_n:  # the state rides beside the K cache
                    kc = {"kv": kc, "ssm": ssm_n[0]}
                # the rows that are not done: the experts route, the
                # state steps and the side part reads (side_impl) them alone
                hidden, kc, vc = forward(
                    params, cfg, token[:, None], offs, kc, vc,
                    decode_attention,
                    **(
                        {"token_mask": ~done[:, None]}
                        if tail or stacked
                        else {}
                    ),
                    **({"stats": stats} if moe_n else {}),
                )
                if ssm_n:
                    kc, ssm_n = kc["kv"], [kc["ssm"]]
                if moe_n:
                    moe_n = [moe_n[0] + stats["moe"]]
                pk, pv = (
                    (kc["side"], vc["side"])
                    if stacked
                    else (kc["pool"], vc["pool"])
                )
                logits = logits_for(params, cfg, hidden[:, 0])
                with jax.named_scope("sample"):
                    split = jax.vmap(jax.random.split)(rngs)
                    rngs, subs = split[:, 0], split[:, 1]
                nxt = sample_token_per_row(
                    logits,
                    subs,
                    temperature,
                    top_k,
                    top_p if use_top_p else None,
                    pres if use_rp else None,
                    repeat_penalty if use_rp else None,
                )
                with jax.named_scope("carry"):
                    nxt = jnp.where(done, jnp.int32(eos), nxt)
                    done = done | (nxt == eos) | (i + 1 >= remaining)
                    if use_rp:
                        pres = pres.at[jnp.arange(b), nxt].set(True)
                    out = out.at[:, i].set(nxt)
                    n_row = jnp.where(prev_done, n_row, i + 1)
                    offs = jnp.where(done, offs, offs + 1)
                return (
                    nxt, offs, pk, pv, rngs, done, i + 1, out, pres, n_row,
                    *moe_n, *ssm_n,
                )

            out0 = jnp.full((b, n_steps), eos, dtype=jnp.int32)
            cache0_k, cache0_v = (
                (side_k, side_v) if stacked else (pool_k, pool_v)
            )
            init = (
                first_tokens,
                offsets,
                cache0_k,
                cache0_v,
                rngs,
                done0,
                jnp.int32(0),
                out0,
                presence,
                jnp.zeros((b,), dtype=jnp.int32),
            )
            n_moe = int("moe_counts" in carry)
            if n_moe:  # a slice counts from zero
                init += (jnp.zeros_like(carry["moe_counts"]),)
            if "ssm" in carry:
                init += (carry["ssm"],)
            (
                token, offs, ck, cv, rngs_out, done, _, out_tokens,
                pres_out, n_row, *tail,
            ) = jax.lax.while_loop(cond, body, init)
            moe_n, ssm_n = tail[:n_moe], tail[n_moe:]
            threaded = (
                {"side_k": ck, "side_v": cv}
                if stacked
                else {"pool_k": ck, "pool_v": cv}
            )
            if moe_n:
                threaded["moe_counts"] = moe_n[0]
            if ssm_n:
                threaded["ssm"] = ssm_n[0]
            new_carry = dict(
                carry,
                tokens=token,
                offsets=offs,
                rngs=rngs_out,
                presence=pres_out,
                done=done,
                remaining=remaining - n_row,
                **threaded,
            )
            return out_tokens, n_row, new_carry

        decode = self._stepped_jit(cfg, carry, decode)
        self._decode_cache[key] = decode
        return decode

    def _spec_batch_decode_step_fn(
        self,
        model: str,
        draft_model: "Optional[str]",
        k: int,
        n_steps: int,
        paged: bool,
        quantized: bool,
        stacked: bool = False,
        carry=None,
        source: str = "model",
        top_k: int = 0,
        use_top_p: bool = False,
    ) -> Callable:
        """Speculative twin of the stepped decode fns (ISSUE 9): per
        slice, ``n_steps`` draft-verify ROUNDS instead of single-token
        steps — each round k sequential draft steps then ONE target
        forward over every live row's k+1 candidate positions, rows
        advancing by their own accepted-prefix length (the loop lives in
        engine/speculative.py::build_spec_step_fn). ``params`` is the
        ``(target, draft)`` pair so the carry keeps the donated slot 1,
        and the jit rides the same hook chain as the plain twins —
        explicit shardings + donation on the TP engine, with the draft
        cache leaves sharded by the DRAFT model's own head count.

        Paged sessions verify NATIVELY (ISSUE 10): ``stacked=True``
        routes the verify's [B,k+1,Hq,D] query block through the
        MULTI-QUERY paged parts kernel (the same ``decode_attention``
        wrapper the plain stacked twin uses — it dispatches on query
        rank) with candidates in the side caches; ``stacked=False``
        (kernel-less fallback) verifies against the gathered pool with
        candidates in the scratch carry leaves and commits the block
        through the table after acceptance. Either way no slack pages
        exist to bill.

        ``source``/``top_k``/``use_top_p`` (ISSUE 16) are compile-time
        statics like the layout flags: the source picks the draft lane
        (``ngram`` has no draft model — ``draft_model`` is None and the
        params pair carries None in the draft slot), and the sampling
        statics shape the sampled rejection-resampling lane exactly
        like the plain stepped twin's cache key does."""
        key = (
            "spec-step", model, draft_model, k, n_steps, paged,
            quantized, stacked, source, top_k, use_top_p,
            self.spec_draft_temperature,
        )
        if key in self._decode_cache:
            return self._decode_cache[key]
        tcfg = self._models[model].cfg
        dcfg = (
            self._models[draft_model].cfg
            if draft_model is not None
            else None
        )
        eos = self._tokenizer_for(model).eos_id
        from .speculative import build_spec_step_fn

        fn = build_spec_step_fn(
            tcfg, dcfg, k, n_steps, eos, paged, quantized,
            stacked=stacked,
            # the DRAFT cache is an unquantized contiguous batch cache:
            # the raw injected kernel applies (never the int8 wrapper —
            # that keys on the TARGET's cache representation)
            draft_decode_attention=self.decode_attention,
            decode_attention=(
                self._paged_decode_attention(tcfg) if stacked else None
            ),
            source=source,
            top_k=top_k,
            use_top_p=use_top_p,
            draft_temperature=self.spec_draft_temperature,
        )
        decode = self._stepped_jit(tcfg, carry, fn, draft_cfg=dcfg)
        self._decode_cache[key] = decode
        return decode

    def decode_open(
        self,
        requests: "list[GenerationRequest]",
        reserve_rows: Optional[int] = None,
        slice_steps: Optional[int] = None,
        spec_accept_floor: Optional[float] = None,
        spec_override=None,
    ):
        """Open an iteration-level decode session over ``requests`` (the
        stepped-decode protocol the continuous scheduler drives —
        engine/stepped.py): all rows prefill now, then the caller runs
        ``session.step(k)`` slices, collecting retired rows' results the
        moment their done-mask sets and joining queued compatible
        requests into the freed slots via ``session.join`` (or the
        resumable ``join_begin``/``join_step``/``join_commit`` chunked
        variant). ``reserve_rows`` sizes the row bucket above
        ``len(requests)`` so a session opened by a lone anchor still has
        free slots for mid-flight joins; ``slice_steps`` overrides the
        compiled slice width (default DECODE_SLICE_STEPS — the
        ``serve --decode-slice-steps`` knob lands here).

        When this engine has a speculative config for the model
        (ctor ``speculative=``, CLI ``--speculative``) and every opening
        request is eligible (repeat_penalty 1 and temperature ≤
        ``spec_temperature_max`` — greedy AND sampled rows since ISSUE
        16), the session runs in DRAFT-VERIFY mode: slices are rounds,
        rows advance by their accepted-prefix length, and the session's
        rolling acceptance drives the per-source auto-fallback policy —
        ``spec_accept_floor`` (default: the engine's ctor value; the
        ``serve --spec-accept-floor`` knob lands here through the
        continuous scheduler). ``spec_override`` forces a specific
        :class:`~.speculative.DraftSpec` instead of the engine's
        resolved config (the solo sampled path uses it to drain one
        request through a private session)."""
        from .stepped import SteppedDecodeSession

        return SteppedDecodeSession.open(
            self, requests, reserve_rows=reserve_rows,
            slice_steps=slice_steps,
            spec_accept_floor=spec_accept_floor,
            spec_override=spec_override,
        )

    @staticmethod
    def _drain_session(session) -> "list[GenerationResult]":
        """Step ``session`` until no row is live and close it, whatever
        happens on the way; the results in the order the rows retired."""
        results: "list[GenerationResult]" = []
        try:
            while session.active:
                results.extend(session.step())
        finally:
            session.close()
        return results

    def _paged_decode_attention(self, cfg: Optional[ModelConfig] = None):
        """The attention impl for paged caches: the parts path (Pallas
        page-table kernel or fused XLA) where specialised kernels are
        enabled (explicit injection, or "auto" on TPU; against the jnp
        gather fallback: not measured in any record PERF.md holds), else
        None (CPU tests). ``cfg`` is unused here;
        the TP engine's override needs it to decide whether the model's
        heads divide the mesh (its shard_map partition rule)."""
        if cfg is not None and cfg.latent:
            # one implementation at every table width and on every
            # platform: the XLA parts path over ONE kv head of group
            # n_heads, keys a row's whole width, values its first
            # kv_lora_rank columns (no Pallas kernel takes that shape)
            from ..ops.pallas_paged_attention import (
                xla_paged_decode_attention_parts,
            )
            from ..models.transformer import latent_score_scale

            def latent_parts(q, kc, vc, lengths):
                return xla_paged_decode_attention_parts(
                    q, kc["pool"], None, kc["table"], lengths,
                    scale=latent_score_scale(cfg),
                    v_width=cfg.kv_lora_rank,
                    owners=kc.get("owners"),
                    # a lightning indexer's selection (models/transformer.py
                    # ``_latent_attend``), where the model has one
                    keep=kc.get("keep"),
                )

            return latent_parts
        if not self._specialised_kernels_enabled():
            return None
        from ..ops.pallas_paged_attention import (
            pallas_paged_decode_attention,
            pallas_paged_decode_attention_mq_parts,
            pallas_paged_decode_attention_mq_parts_int8,
            pallas_paged_decode_attention_parts,
            pallas_paged_decode_attention_parts_int8,
            pallas_paged_pool_parts,
            xla_paged_decode_attention_parts,
            xla_paged_decode_attention_parts_int8,
        )

        def decode_attention(q, kc, vc, lengths):
            # int8 pools are {"q","s"} dicts (engine/paged_kv.py); both
            # parts impls have a quantized twin with the same (acc, m, l)
            # contract, so the width/Jmax policy below applies unchanged.
            quant = isinstance(kc["pool"], dict)
            if q.ndim == 4:
                # MULTI-QUERY verify block [B, k+1, Hq, D] (ISSUE 10):
                # one kernel pass streams each row's prompt pages once
                # for all candidate positions. ``offsets`` reconstruct
                # the absolute position of query 0 from the stacked
                # leaf's row vectors (the per-query causal cut is inert
                # over prompt pages — every candidate sits past the
                # prompt — but the kernel contract is the general one).
                offsets = kc["write_pos"] + kc["prompt_lens"]
                if quant:
                    return pallas_paged_decode_attention_mq_parts_int8(
                        q,
                        kc["pool"]["q"], kc["pool"]["s"],
                        vc["pool"]["q"], vc["pool"]["s"],
                        kc["table"], lengths, offsets,
                        layer=kc.get("layer"),
                    )
                return pallas_paged_decode_attention_mq_parts(
                    q, kc["pool"], vc["pool"], kc["table"], lengths,
                    offsets, layer=kc.get("layer"),
                )
            if "side" in kc:  # stacked-hybrid mode: unnormalised parts
                # for the caller's merge (transformer.py). THREE parts
                # impls (paged_parts_impl): where the page table is
                # NARROW, the held-page kernel where the step built the
                # list of the held pages (``owners.order``: the whole
                # stacked pool and the layer's entry in it), else the
                # fused-XLA variant, over the pool in place where the
                # step built the table's inverse (``owners``: no page
                # has two readers), else over the table's gathered
                # pages; the page-table kernel — whose per-cell skip
                # bounds each row's work by its own pages — where the
                # table is wide. Every benchmark cell compiles one of
                # the first two (tables 4 wide; PERF.md §5); the table
                # kernel against them, and where they cross: not
                # measured (ROADMAP D5). The pool is a per-layer xs
                # slice unless a "layer" index says it is the whole
                # stacked pool (the two kernels').
                owners = kc.get("owners")
                if owners is not None and owners.order is not None:
                    return pallas_paged_pool_parts(
                        q, kc["pool"], vc["pool"], kc["layer"], lengths,
                        owners,
                    )
                if (
                    kc.get("layer") is None
                    and paged_parts_impl(q.shape[0], kc["table"].shape[1])
                    == "xla"
                ):
                    if quant:
                        return xla_paged_decode_attention_parts_int8(
                            q,
                            kc["pool"]["q"], kc["pool"]["s"],
                            vc["pool"]["q"], vc["pool"]["s"],
                            kc["table"], lengths,
                            owners=kc.get("owners"),
                        )
                    return xla_paged_decode_attention_parts(
                        q, kc["pool"], vc["pool"], kc["table"], lengths,
                        owners=kc.get("owners"),
                    )
                if quant:
                    return pallas_paged_decode_attention_parts_int8(
                        q,
                        kc["pool"]["q"], kc["pool"]["s"],
                        vc["pool"]["q"], vc["pool"]["s"],
                        kc["table"], lengths,
                        layer=kc.get("layer"),
                    )
                return pallas_paged_decode_attention_parts(
                    q,
                    kc["pool"],
                    vc["pool"],
                    kc["table"],
                    lengths,
                    layer=kc.get("layer"),
                )
            return pallas_paged_decode_attention(
                q, kc["pool"], vc["pool"], kc["table"], lengths
            )

        return decode_attention

    def _paged_decode_impl(
        self,
        cfg: ModelConfig,
        rows: int,
        table_width: int,
        shared_pages: bool,
        pool: Any = None,
    ) -> str:
        """Name of the attention a paged decode step at these static
        shapes compiles to — the ONE rule: the step function builds the
        table's inverse by it and ``/debug/state`` reports it.
        ``"gather"`` (no kernel — jnp gather through the table), else
        the stacked parts implementation :func:`paged_parts_impl`
        selects for the stacked pool leaf ``pool``; its ``"xla"`` is
        ``"xla-pool"`` (the pool read in place, pages named by pool
        index) unless ``shared_pages``: a page several rows' tables hold
        has no one owner, and the table's pages are gathered as before.
        A latent pool (one compressed row a token) is XLA's at every
        width; the held-page kernel takes pages with one reader only."""
        if self._paged_decode_attention(cfg) is None:
            return "gather"
        impl = "xla" if cfg.latent else paged_parts_impl(
            rows, table_width, None if shared_pages else pool
        )
        if impl == "xla" and not shared_pages:
            return "xla-pool"
        return impl

    def _contiguous_row_bytes(
        self, cfg: ModelConfig, s_bucket: int, g_bucket: int
    ) -> int:
        """K+V bytes ONE row pins in a contiguous batch cache — every
        row is padded to the widest prompt bucket + widest generation
        bucket (that IS the allocation). Under kv_quantize the decode
        cache is int8 codes + one f32 scale per (position, head) vector,
        so a column costs D+4 bytes instead of 2·D."""
        return cfg.cache_layers * (
            s_bucket + g_bucket
        ) * self._kv_token_bytes(cfg) + self._state_row_bytes(cfg)

    def _state_row_bytes(self, cfg: ModelConfig) -> int:
        """Bytes ONE row's recurrent state takes, whatever the row's
        length (0 for a model without state-space layers): what admission
        adds to a row's bytes a token."""
        return cfg.state_bytes_per_row(jnp.dtype(self.dtype).itemsize)

    def _kv_token_bytes(
        self, cfg: ModelConfig, widths: "Optional[Tuple[int, int]]" = None
    ) -> int:
        """Bytes ONE token takes in ONE attention block's cache, K and V
        leaves ``widths`` wide (default: the config's own cache widths —
        K and V heads, or a latent cache's one row and no V). Under
        kv_quantize a row is int8 codes + one f32 scale per vector."""
        kw, vw = widths or (cfg.cache_k_width, cfg.cache_v_width)
        if self.kv_quantize:
            per_head = sum(w + 4 for w in (kw, vw) if w)
        else:
            per_head = (kw + vw) * jnp.dtype(self.dtype).itemsize
        return cfg.cache_heads * per_head

    def _paged_chunk_bytes(
        self,
        cfg: ModelConfig,
        chunk_pages: "list[int]",
        b_bucket: int,
        g_bucket: int,
        stacked: bool,
    ) -> int:
        """K+V bytes one paged sub-batch is BILLED: a pow2-rounded page
        pool of the rows' own pages plus two (each row billed its OWN
        pages — the per-row-pages economics the pool exists for), at the
        pool's lane-padded widths and int8 codes + f32 scales when
        quantized, plus, in stacked mode, the per-row side caches. An
        estimate, and a low one: what allocates is the session
        (``SteppedDecodeSession._open_paged``), whose pool is
        ``pow2(2 x (pages + parking))`` for the joins' headroom — up to
        twice the pages billed here. The arithmetic is kept as it is
        because ``max_admission_rows`` turns it into every served cell's
        row cap (ROADMAP D6 holds the gap)."""
        page = self.page_size
        from .paged_kv import pool_widths

        total = sum(chunk_pages) + 2  # + shared garbage/pad pages
        n_pages = 4
        while n_pages < total:
            n_pages *= 2
        pool_bytes = (
            cfg.cache_layers * n_pages * page
            * self._kv_token_bytes(cfg, pool_widths(cfg, stacked))
        )
        # the recurrent state is allocated for the whole row bucket
        state_bytes = b_bucket * self._state_row_bytes(cfg)
        if not stacked:
            return pool_bytes + state_bytes
        side_bytes = (
            cfg.cache_layers * b_bucket * g_bucket * self._kv_token_bytes(cfg)
        )
        return pool_bytes + side_bytes + state_bytes

    def _max_batch_rows(
        self,
        cfg: ModelConfig,
        requests: "list[GenerationRequest]",
        all_prompt_ids: "list[list[int]]",
    ) -> int:
        """Widest batch bucket whose estimated K+V footprint fits
        BATCH_KV_BUDGET_BYTES (floor: BATCH_MIN_SPLIT_ROWS, the old hard
        cap, known-safe at max context). A step reads the weights once
        for all its rows, so the right sub-batch width is a memory
        decision, not a constant: 128 short-prompt rows run as ONE
        decode loop, while a fleet of max-context requests still splits
        to the known-safe width.

        Contiguous batches bill EVERY row at the widest shape (the
        shared cache allocation). Paged batches bill each row its own
        pages (``paged_kv.pages_pinned``) and validate every sequential
        chunk of a candidate width against :meth:`_paged_chunk_bytes` —
        so a mixed-length fleet admits more rows per decode window
        under paging, and more again under paged+int8 (~(D+4)/2D the
        page bytes)."""
        g_bucket = _bucket(
            max(r.max_new_tokens for r in requests), GEN_BUCKETS
        )
        if self.paged_kv:
            from .paged_kv import pages_pinned

            stacked = self._paged_decode_attention(cfg) is not None
            rows_pages = [
                pages_pinned(
                    len(ids), r.max_new_tokens, self.page_size, stacked
                )
                for r, ids in zip(requests, all_prompt_ids)
            ]
            return self._paged_rows_cap(cfg, rows_pages, g_bucket, stacked)
        max_rows = BATCH_MIN_SPLIT_ROWS
        s_bucket = max(
            _prompt_alloc(len(ids)) for ids in all_prompt_ids
        )
        bytes_per_row = self._contiguous_row_bytes(cfg, s_bucket, g_bucket)
        for b in BATCH_BUCKETS:
            if b > max_rows and b * bytes_per_row <= BATCH_KV_BUDGET_BYTES:
                max_rows = b
        return max_rows

    def _paged_rows_cap(
        self,
        cfg: ModelConfig,
        rows_pages: "list[int]",
        g_bucket: int,
        stacked: bool,
    ) -> int:
        """Widest batch bucket whose paged pool+side bytes fit the
        budget for the given PER-ROW page bill — factored out so the
        admission estimator can bill shared-prefix sharers their OWN
        pages only (:meth:`max_admission_rows`) while the batch
        splitter bills every row in full (rows that open a session
        together prefill together and share no pages)."""
        max_rows = BATCH_MIN_SPLIT_ROWS
        for b in BATCH_BUCKETS:
            if b <= max_rows:
                continue
            chunks = [
                rows_pages[i : i + b]
                for i in range(0, len(rows_pages), b)
            ]
            if all(
                self._paged_chunk_bytes(
                    cfg,
                    chunk,
                    _bucket(len(chunk), BATCH_BUCKETS),
                    g_bucket,
                    stacked,
                )
                <= BATCH_KV_BUDGET_BYTES
                for chunk in chunks
            ):
                max_rows = b
        return max_rows

    def max_admission_rows(self, request: GenerationRequest) -> int:
        """Budget-aware ADMISSION cap for a continuous-batching window
        anchored by ``request`` (consumed by serve/scheduler.py): the
        widest batch bucket whose estimated K+V footprint — at this
        request's prompt/generation buckets, under this engine's cache
        layout (contiguous / paged × bf16 / int8-KV) — fits
        BATCH_KV_BUDGET_BYTES. A pure estimate: no weights load, nothing
        allocates. Denser cache modes therefore ADMIT larger fleets at
        the same budget instead of stopping at the scheduler's static
        cap — the serving half of the paged×int8 capacity story."""
        model = request.model
        cfg = (
            self.registry[model]
            if model in self.registry
            else get_model_config(model)
        )
        ids = self._tokenizer_for(model).encode(request.prompt)
        width = max(BATCH_BUCKETS)
        # Speculative sessions (ISSUE 9/10): paged rows bill EXACTLY the
        # plain-decode page count — the native verify keeps candidates
        # in the side caches / scratch leaves, so there is no slack and
        # no spec-specific paged arm here (the generic `_max_batch_rows`
        # below prices spec and plain rows identically — the no-
        # admission-tax point of ISSUE 10). Contiguous rows still carry
        # the _spec_margin in their cache shape plus the draft's own
        # (tiny, unquantized) batch cache.
        spec = (
            self._resolve_spec(model) if self._spec_eligible(request) else None
        )
        if self.paged_kv and ids and self.prefix_share:
            # Shared-prefix billing (ISSUE 7): under prefix sharing a
            # fleet anchored by this request shares the prompt's full
            # page-aligned pages — the FIRST row pays them, every later
            # sharer is billed only its divergent-tail pages (here: the
            # boundary CoW page + generation pages). The session-level
            # pool accounting enforces the same rule exactly
            # (can_join/join_begin); this estimate just stops the row
            # cap from under-admitting the fleet the pool can hold.
            from .paged_kv import pages_pinned

            page = self.page_size
            stacked = self._paged_decode_attention(cfg) is not None
            need = pages_pinned(
                len(ids), request.max_new_tokens, page, stacked
            )
            shared = min((len(ids) - 1) // page, need - 1)
            rows_pages = [need] + [need - shared] * (width - 1)
            g_bucket = _bucket(request.max_new_tokens, GEN_BUCKETS)
            return self._paged_rows_cap(cfg, rows_pages, g_bucket, stacked)
        if spec is not None and not self.paged_kv:
            g_bucket = _bucket(request.max_new_tokens, GEN_BUCKETS)
            s_bucket = _prompt_alloc(max(len(ids), 1))
            margin = _spec_margin(spec.k)
            bytes_per_row = self._contiguous_row_bytes(
                cfg, s_bucket + margin, g_bucket
            )
            if spec.draft is not None:
                # model/cross sources add the draft's own (tiny,
                # unquantized) batch cache; ngram adds only an int32
                # history row — negligible next to the KV payload
                try:
                    dcfg = (
                        self.registry[spec.draft]
                        if spec.draft in self.registry
                        else get_model_config(spec.draft)
                    )
                    itemsize = jnp.dtype(self.dtype).itemsize
                    bytes_per_row += (
                        dcfg.cache_layers
                        * (s_bucket + g_bucket + margin)
                        * dcfg.kv_values_per_token * itemsize
                    )
                except Exception:  # noqa: BLE001 — estimate only
                    pass
            max_rows = BATCH_MIN_SPLIT_ROWS
            for b_ in BATCH_BUCKETS:
                if (
                    b_ > max_rows
                    and b_ * bytes_per_row <= BATCH_KV_BUDGET_BYTES
                ):
                    max_rows = b_
            return max_rows
        return self._max_batch_rows(cfg, [request] * width, [ids] * width)

    def generate_batch(
        self, requests: "list[GenerationRequest]"
    ) -> "list[GenerationResult]":
        """Generate for several requests in one batched decode.

        Prefill runs grouped by prompt bucket (see :meth:`_batch_states`);
        decode runs all rows together, reading the weights from HBM once
        per step for the whole batch. The weight stream amortises over
        rows but KV/cache-update/sampling traffic scales with them, so
        aggregate throughput grows sublinearly (by how much: not
        measured on the chip).

        On a paged engine each memory-bounded chunk of the fleet is a
        stepped decode session (:meth:`decode_open`, the one the
        continuous scheduler opens) run to its end: rows retire in the
        slice that finishes them, results return in request order.

        Per-row rng streams, offsets and sampling knobs make each row's
        output token-identical to ``generate(request)`` alone. Constraints:
        all requests must name the same model and share ``top_k`` (it is
        baked into the compiled loop's shape).

        Each result's ``decode_s`` is the *batch* decode wall-time (the rows
        ran together and are not separable); ``prefill_s`` follows the same
        convention — rows whose prefills grouped into one padded forward
        (:meth:`_batch_states`) share that group's wall-clock, while
        fallback rows (multi-chunk prompts, prefix hits) report their own
        solo window. Summing per-row ``prefill_s`` over a group therefore
        multiply-counts the shared window, exactly as summing ``decode_s``
        would. A paged row reports what its session does: ``decode_s``
        from the session's open to the end of the slice that retired the
        row, ``total_s`` from the row's prefill start to that point.
        """
        if not requests:
            return []
        models = {r.model for r in requests}
        if len(models) > 1:
            raise ValueError(f"one model per batch, got {sorted(models)}")
        top_ks = {r.top_k for r in requests}
        if len(top_ks) > 1:
            raise ValueError(f"one top_k per batch, got {sorted(top_ks)}")
        model, top_k = requests[0].model, requests[0].top_k
        self.load_model(model)
        cfg = self._models[model].cfg

        tok = self._tokenizer_for(model)
        all_prompt_ids = [tok.encode(r.prompt) for r in requests]
        max_rows = self._max_batch_rows(cfg, requests, all_prompt_ids)
        if len(requests) > max_rows:
            # Larger fleets run as sequential full-width batches rather
            # than blowing past the memory-bounded shape. Prompts are
            # tokenized exactly once — the chunks reuse the id slices.
            results = []
            for i in range(0, len(requests), max_rows):
                results.extend(
                    self._generate_batch_chunk(
                        requests[i : i + max_rows],
                        all_prompt_ids[i : i + max_rows],
                    )
                )
            return results
        return self._generate_batch_chunk(requests, all_prompt_ids)

    def _generate_batch_chunk(
        self,
        requests: "list[GenerationRequest]",
        all_prompt_ids: "list[list[int]]",
    ) -> "list[GenerationResult]":
        """One memory-bounded sub-batch of :meth:`generate_batch`
        (already validated; prompts already tokenized)."""
        if self.paged_kv:
            # A paged row is a session's row: the chunk is a stepped
            # session (the one the continuous scheduler opens) run to
            # its end. One id for the whole session, so that a consumer
            # still counts this chunk as one decode window. Results go
            # back by request; a list each, since one request object
            # may stand in the fleet twice.
            window_id = next(_DECODE_WINDOW_IDS)
            retired: "Dict[int, list[GenerationResult]]" = {}
            for res in self._drain_session(self.decode_open(requests)):
                res.extras["decode_window"] = window_id
                retired.setdefault(id(res.request), []).append(res)
            return [retired[id(r)].pop() for r in requests]

        model, top_k = requests[0].model, requests[0].top_k
        cfg = self._models[model].cfg
        self._refuse_contiguous_rows(model, cfg)
        tok = self._tokenizer_for(model)
        # One cache shape for every row: widest prompt bucket + widest
        # generation bucket.
        s_buckets = [_prompt_alloc(len(ids)) for ids in all_prompt_ids]
        g_bucket = _bucket(max(r.max_new_tokens for r in requests), GEN_BUCKETS)
        cache_len = max(s_buckets) + g_bucket
        if cache_len > cfg.max_seq_len:
            raise ValueError(
                f"{model}: batch cache {cache_len} exceeds max_seq_len "
                f"{cfg.max_seq_len}"
            )

        states = self._batch_states(
            requests,
            all_prompt_ids,
            [cache_len] * len(requests),
            group_refs=True,
        )
        n = len(states)
        b_bucket = _bucket(n, BATCH_BUCKETS)
        use_top_p = any(st["use_top_p"] for st in states)
        use_rp = any(st["use_rp"] for st in states)
        # Grouped rows assemble by per-group gather + permutation take
        # (st["group"] refs) instead of per-row slices: at 128 rows the
        # slice-and-concat chain is ~260 host dispatches that drain
        # inside the decode window (their cost: not measured on the
        # chip). Padding rows replicate row 0 and enter pre-done.
        asm = self._assemble_rows(
            states,
            b_bucket,
            self._row_field_specs(states)
            + [
                (
                    "k", "k", 1,
                    lambda rows: jnp.concatenate(
                        [states[r]["k_cache"] for r in rows], axis=1
                    ),
                ),
                (
                    "v", "v", 1,
                    lambda rows: jnp.concatenate(
                        [states[r]["v_cache"] for r in rows], axis=1
                    ),
                ),
            ],
        )
        first_tokens = asm["first"]
        presence = asm["presence"]
        rngs = asm["rng"]
        k_cache = asm["k"]
        v_cache = asm["v"]
        # group caches are consumed; free the bucket-padded prefill
        # arrays before the decode loop allocates (see _assemble_rows)
        for st in states:
            st.pop("group", None)
        asm = None
        if self.kv_quantize:
            k_cache, v_cache = self._quantize_batch_cache(
                model, k_cache, v_cache
            )
        offsets = jnp.asarray(
            [st["s_real"] for st in states]
            + [states[0]["s_real"]] * (b_bucket - n),
            dtype=jnp.int32,
        )
        temps = jnp.asarray(
            [r.temperature for r in requests]
            + [requests[0].temperature] * (b_bucket - n),
            dtype=jnp.float32,
        )
        # Rows that disabled nucleus filtering (top_p == 1.0) get a sentinel
        # of 2.0: with the filter statically enabled for the whole batch
        # (use_top_p = any row), cum_excl < 2.0 is exactly all-True, so the
        # filter is a provable identity for those rows — float32 cumsum
        # error near 1.0 could otherwise mask tail tokens and change their
        # draw vs a lone generate().
        def _row_top_p(r: GenerationRequest) -> float:
            return r.top_p if r.top_p < 1.0 else 2.0

        top_ps = jnp.asarray(
            [_row_top_p(r) for r in requests]
            + [_row_top_p(requests[0])] * (b_bucket - n),
            dtype=jnp.float32,
        )
        rps = jnp.asarray(
            [r.repeat_penalty for r in requests]
            + [requests[0].repeat_penalty] * (b_bucket - n),
            dtype=jnp.float32,
        )
        done0 = jnp.asarray([False] * n + [True] * (b_bucket - n))
        n_real = max(r.max_new_tokens for r in requests) - 1

        t1 = time.monotonic()
        if n_real > 0:
            decode = self._batch_decode_fn(
                model, g_bucket, top_k, use_top_p, use_rp
            )
            out, n_row = decode(
                self._models[model].params,
                first_tokens,
                offsets,
                k_cache,
                v_cache,
                temps,
                rngs,
                jnp.int32(n_real),
                top_ps,
                rps,
                presence,
                done0,
            )
            out = jax.block_until_ready(out)
            n_row = _to_host_list(n_row)
        else:
            out = jnp.zeros((b_bucket, 0), dtype=jnp.int32)
            n_row = [0] * b_bucket
        t2 = time.monotonic()
        window_id = next(_DECODE_WINDOW_IDS)

        # batched transfers: whole-array host copies, not per-int reads
        # (see generate())
        out_host = _to_host_list(out)
        first_host = _to_host_list(first_tokens)
        results = []
        for r, (request, st) in enumerate(zip(requests, states)):
            budget = request.max_new_tokens - 1
            take = min(n_row[r], budget)
            generated = [int(first_host[r])] + out_host[r][:take]
            if request.stop_at_eos and tok.eos_id in generated:
                generated = generated[: generated.index(tok.eos_id)]
            text = tok.decode(generated)
            if request.stop:
                generated, text = _apply_stop(generated, text, tok, request.stop)
            prefill_s = st["t1"] - st["t0"]  # this row's own prefill
            results.append(
                GenerationResult(
                    request=request,
                    tokens=generated,
                    text=text,
                    prompt_tokens=st["s_real"],
                    generated_tokens=len(generated),
                    prefill_s=prefill_s,
                    decode_s=t2 - t1,  # the shared batch decode window
                    total_s=prefill_s + (t2 - t1),
                    extras={"decode_window": window_id},
                )
            )
        self._observe_batch_window(model, results, t1, t2)
        return results

    def generate_stream(
        self, request: GenerationRequest, chunk_tokens: int = DEFAULT_STREAM_CHUNK
    ):
        """Incremental generation: decode in compiled chunks of
        ``chunk_tokens`` steps, yielding a :class:`GenerationChunk` after
        each. The decode state (KV cache, rng, presence mask) threads
        through the chunk calls, so the token stream is *identical* to the
        monolithic :meth:`generate` for the same request — streaming only
        bounds latency-to-first-text, it does not change the sample path.

        Note on text deltas: each chunk's ``text`` decodes only that chunk's
        tokens; a multi-byte UTF-8 character split across chunks may render
        as a replacement char at the boundary. The final ``done`` chunk's
        ``result.text`` decodes the full stream and is authoritative.
        """
        st = self._maybe_quantize_cache(self._start(request))
        eos = st["tok"].eos_id
        chunk_bucket = _bucket(min(chunk_tokens, request.max_new_tokens), GEN_BUCKETS)
        decode = self._decode_fn(
            request.model,
            chunk_bucket,
            request.top_k,
            st["use_top_p"],
            st["use_rp"],
        )

        generated = [int(st["first"][0])]
        # The monolithic decode loop only stops on an EOS *sampled inside
        # the loop* (the first token enters the loop as input, EOS or not);
        # mirror that exactly so the chunked token stream is identical.
        # When stop_at_eos, an EOS first token means nothing will ever be
        # visible — end the stream instead of burning decode chunks.
        stop = request.stop_at_eos and generated[0] == eos

        # Stop-string handling works on the CUMULATIVE decode of all
        # streamed tokens (per-chunk decodes can split multi-byte chars and
        # would corrupt the match): the stream ends as soon as the text
        # contains any request.stop string, deltas are cut right before it,
        # and a trailing replacement char (a possibly-incomplete multi-byte
        # sequence) is held back until more tokens resolve it. The
        # done-chunk's result applies the identical cut via _finish, so
        # stream and result agree.
        emitted_text = ""
        pending_tokens: "list[int]" = []  # ids not yet attached to a chunk

        def stop_delta(all_tokens: "list[int]") -> "tuple[str, bool]":
            nonlocal emitted_text
            cum = st["tok"].decode(all_tokens)
            cuts = [cum.find(s) for s in request.stop if s in cum]
            hit = bool(cuts)
            if hit:
                cum = cum[: min(cuts)]
            display = cum
            if not hit:
                # hold back (a) a trailing replacement char — a possibly
                # incomplete multi-byte sequence — and (b) any suffix that
                # is a prefix of a stop string: emitting it now would leak
                # text the next chunk may reveal to be part of the stop.
                if display.endswith("�"):
                    display = display[:-1]
                hold = 0
                for s in request.stop:
                    for n in range(min(len(s) - 1, len(display)), 0, -1):
                        if display.endswith(s[:n]):
                            hold = max(hold, n)
                            break
                if hold:
                    display = display[:-hold]
            if display.startswith(emitted_text):
                delta = display[len(emitted_text):]
            elif len(display) > len(emitted_text):
                # a tokenizer whose decode is not prefix-stable (HF
                # cleanup/joining) rewrote earlier text; keep streaming from
                # the same length rather than silently dropping the rest —
                # the done-chunk's result stays authoritative
                delta = display[len(emitted_text):]
            else:
                delta = ""
            emitted_text += delta
            return delta, hit

        if not stop:
            visible = list(generated)
            if not request.stop:
                # no stop strings: every token streams, even ones that
                # decode to no text (extra-vocab ids)
                yield GenerationChunk(
                    text=st["tok"].decode(visible), tokens=visible
                )
            else:
                delta, hit = stop_delta(list(generated))
                pending_tokens.extend(visible)
                if delta:
                    yield GenerationChunk(text=delta, tokens=pending_tokens)
                    pending_tokens = []
                stop = stop or hit

        token = st["first"]
        offset = jnp.int32(st["s_real"])
        k_cache, v_cache = st["k_cache"], st["v_cache"]
        presence, rng = st["presence"], st["rng"]
        remaining = request.max_new_tokens - 1
        while remaining > 0 and not stop:
            n = min(chunk_bucket, remaining)
            out, n_done, k_cache, v_cache, presence, rng = decode(
                st["tf"].params,
                token,
                offset,
                k_cache,
                v_cache,
                jnp.float32(request.temperature),
                rng,
                jnp.int32(n),
                jnp.float32(request.top_p),
                jnp.float32(request.repeat_penalty),
                presence,
            )
            n_done = int(n_done)
            chunk_ids = _to_host_list(out[0][:n_done])
            if not chunk_ids:
                break
            generated.extend(chunk_ids)
            remaining -= n_done
            offset = offset + jnp.int32(n_done)
            token = out[:, n_done - 1]
            emit = list(chunk_ids)
            if eos in chunk_ids:
                # decode's done-mask stopped the loop; the monolithic path
                # stops at the same step.
                stop = True
                if request.stop_at_eos:
                    emit = emit[: emit.index(eos)]
            if emit:
                if not request.stop:
                    yield GenerationChunk(
                        text=st["tok"].decode(emit), tokens=emit
                    )
                else:
                    delta, hit = stop_delta(list(generated))
                    pending_tokens.extend(emit)
                    if delta:
                        yield GenerationChunk(
                            text=delta, tokens=pending_tokens
                        )
                        pending_tokens = []
                    if hit:
                        stop = True

        if request.stop:
            # flush any held-back trailing text so the streamed deltas sum
            # to exactly the final result's text
            final_tokens = list(generated)
            eos_pos = (
                final_tokens.index(eos)
                if request.stop_at_eos and eos in final_tokens
                else len(final_tokens)
            )
            cum = st["tok"].decode(final_tokens[:eos_pos])
            cuts = [cum.find(s) for s in request.stop if s in cum]
            if cuts:
                cum = cum[: min(cuts)]
            if len(cum) > len(emitted_text):
                yield GenerationChunk(
                    text=cum[len(emitted_text):], tokens=pending_tokens
                )
                pending_tokens = []
            elif pending_tokens:
                # text ended exactly at the cut but ids are still owed to
                # the wire (chunk.tokens contract)
                yield GenerationChunk(text="", tokens=pending_tokens)
                pending_tokens = []

        t2 = time.monotonic()
        yield GenerationChunk(
            text="",
            tokens=[],
            done=True,
            result=self._finish(request, generated, st, t2),
        )
