"""Iteration-level decode sessions: admit and retire rows at decode-step
granularity.

The window scheduler dispatches a whole batch to completion: a request
arriving 10 ms after a window closes waits for the slowest row of the
previous batch, and the engine keeps stepping EOS-finished rows (writing
padding EOS tokens) until every row is done. This module is the engine
half of the fix (Orca's iteration-level scheduling, Yu et al. OSDI '22,
composed with vLLM-style paged block management, Kwon et al. SOSP '23):

- :meth:`SteppedDecodeSession.open` prefills the initial rows (the
  grouped-prefill machinery, ``_batch_states``) and assembles a
  resumable batched decode state at a fixed row bucket. On a paged
  engine this is the ONLY code that builds a page pool and ``step`` the
  only code that decodes over one: the continuous scheduler and a paged
  ``generate_batch`` (which opens a session on each chunk and drains
  it, ``JaxEngine._drain_session``) both come through ``decode_open``;
- :meth:`SteppedDecodeSession.step` runs one bounded slice (8–16 steps,
  ``DECODE_SLICE_STEPS``) through the stepped decode fns
  (``_batch_decode_step_fn`` / ``_paged_batch_decode_step_fn``, which
  return the full loop carry), then RETIRES rows whose done-mask is set
  — their result returns immediately and, on the paged path, their pages
  go back to the pool mid-flight;
- :meth:`SteppedDecodeSession.join` admits a queued compatible request
  into a freed slot between slices: solo prefill at the session's cache
  shape, scattered into the slot (contiguous) or into freshly allocated
  pool pages (paged);
- the CHUNKED variant — :meth:`SteppedDecodeSession.join_begin` /
  :meth:`join_step` / :meth:`join_commit` — splits that prefill into
  token-budgeted chunks (the engine's offset>0 chunked-prefill path,
  ``_prompt_chunks``) so the scheduler can interleave one chunk per
  decode slice: in-flight rows' stall per slice is bounded by the chunk
  budget (``--prefill-chunk-tokens``) instead of the joiner's prompt
  length (Sarathi-Serve's chunked-prefill argument, Agrawal et al.
  OSDI '24, applied to mid-flight admission). The pending joiner's KV
  accumulates in a private solo cache across chunks; the row enters the
  session's done-mask bookkeeping only at commit, which samples the
  first token and scatters the cache exactly as the one-shot join.

Token parity: every row's stream is bit-identical to its solo
``generate()`` — the slice loop is the monolithic batch loop with the
carry threaded across calls (the same argument that makes
``generate_stream`` identical to ``generate``), per-row rng/knob/done
machinery is shared with the batch paths, and rows are mathematically
independent across the batch dimension, so retiring one row or joining
another never perturbs a companion's tokens. The per-row ``remaining``
budget folded into the done mask only cuts tokens the monolithic path
samples and then discards.

Shapes stay static per session: the row bucket, cache length (or page
pool + table width) and slice width are fixed at open; joins must fit
them (``can_join``) or they anchor a later session instead — the
"bucketed prefill-then-join" discipline.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..models.config import MIXER_KDA
from ..models.kda import kda_step_impl
from ..models.quantize import KV_INT8_LEVELS, quantize_kv_cache
from ..models.ssm import init_state, install_state_row, ssm_step_impl, state_bytes
from ..models.transformer import is_state_cache, moe_block_rows, moe_impl, side_impl
from ..ops.pallas_paged_side import side_block
from ..obs.detect import observe_retired_tokens, observe_slice_tokens
from ..obs.metrics import enabled as _obs_enabled
from ..obs.trace import TRACER
from ..utils.compile_cache import compile_count
from .backend import GenerationRequest, GenerationResult, UnsupportedMechanism


# ``sched.slice`` attributes of an expert model's decode slice, in the
# order of the carry's ``moe_counts`` leaf: token-expert pairs on held,
# identity and absent experts, held experts with at least one pair, and
# blocks of pairs (an expert's weights are read once a block: ``moe_blocks``
# counts weight reads where ``moe_touched`` counts experts), each summed
# over the slice's steps and layers. Beside them go
# ``moe_steps`` (steps the slice ran) and ``moe_tokens`` (tokens its live
# rows produced): held + zero + absent = moe_tokens x layers x top_k.
MOE_COUNT_NAMES = (
    "moe_held", "moe_zero", "moe_absent", "moe_touched", "moe_blocks",
)


def _pow2_at_least(n: int, floor: int = 1) -> int:
    m = floor
    while m < n:
        m *= 2
    return m


def _set_row(cache, r: int, row, axis: int = 1):
    """Write one row of a (possibly ``{"q","s"}``-leafed) batch cache:
    ``row`` carries a singleton batch dim at ``axis``."""
    if isinstance(cache, dict):
        return {
            k: _set_row(cache[k], r, row[k], axis) for k in cache
        }
    idx = [slice(None)] * cache.ndim
    idx[axis] = r
    return cache.at[tuple(idx)].set(
        jnp.take(row, 0, axis=axis), mode="drop"
    )


def _zero_row(cache, r: int, axis: int = 1):
    """Zero one row of a (possibly dict-leafed) batch cache."""
    if isinstance(cache, dict):
        return {k: _zero_row(cache[k], r, axis) for k in cache}
    idx = [slice(None)] * cache.ndim
    idx[axis] = r
    return cache.at[tuple(idx)].set(0, mode="drop")


# ``row["ints"]`` of the row program below: these six, then the row's
# page-table entries (a paged carry), then the destination page of each
# page of the private cache (a paged install).
_ROW_INTS = ("slot", "s_real", "first", "offsets", "prompt_len", "remaining")


def _row_program(row, carry):
    """Seat one row in a session's carry: the ONE writer of a row's
    control leaves, and with the row's private prefill cache the whole
    install of a joiner, as one device program. Jitted by the engine
    (``_row_install_fn``) with ``carry`` donated where the slice step's
    is, so the pool's pages are written where they lie.

    ``row`` holds ``ints`` (int32, ``_ROW_INTS`` and what follows them),
    ``knobs`` (float32: temperature, the top-p sentinel, repeat
    penalty, and the 127 an int8 cache's scales divide by: a runtime
    value, see ``quantize_kv_vector``), ``rng`` (the row's key),
    ``presence`` (``[1, vocab]``) and, for an install, ``k`` / ``v``:
    the private cache as the prefill chunks left it (``[L, 1, Hkv,
    alloc, D]``), with ``ssm``, the joiner's recurrent state after its
    last real token, where the model has state-space layers (the row
    starts from ITS state, not the slot's last owner's). Every number is traced, the slot and the page ids
    too, so an executable is keyed by shapes alone; the body
    specialises at trace time on what it can see of its arguments: a
    paged carry or a batch cache, a ``{"q", "s"}`` leaf or an array,
    side caches or the scalar sentinel, a cache to install or none (a
    swapped-in row's payload is already in place). A slot or a page
    outside the carry is dropped with its update: the session compiles
    the program at open by running it on slot ``b_bucket``, which
    writes nothing."""
    from .paged_kv import install_pages

    out = dict(carry)
    ints = row["ints"]
    r, s_real, first, offsets, prompt_len, remaining = (
        ints[i] for i in range(len(_ROW_INTS))
    )
    knobs = row["knobs"]
    jmax = carry["table"].shape[1] if "table" in carry else 0
    if jmax:
        table_row = ints[len(_ROW_INTS) : len(_ROW_INTS) + jmax]
        out["table"] = carry["table"].at[r].set(table_row, mode="drop")
    if "ssm" in row:
        out["ssm"] = install_state_row(carry["ssm"], r, row["ssm"])
    if "k" in row and jmax:
        out["pool_k"], out["pool_v"] = install_pages(
            carry["pool_k"], carry["pool_v"], row["k"], row["v"],
            s_real, ints[len(_ROW_INTS) + jmax :], knobs[3],
        )
        for key in ("side_k", "side_v"):
            side = carry[key]
            if isinstance(side, dict) or side.ndim:  # stacked session
                out[key] = _zero_row(side, r)
    elif "k" in row:
        kc_row, vc_row = row["k"], row["v"]
        if isinstance(carry["k_cache"], dict):
            kc_row, vc_row = quantize_kv_cache(kc_row, vc_row, knobs[3])
        out["k_cache"] = _set_row(carry["k_cache"], r, kc_row)
        out["v_cache"] = _set_row(carry["v_cache"], r, vc_row)
    for key, value in (
        ("tokens", first),
        ("rngs", row["rng"]),
        ("presence", row["presence"][0]),
        ("offsets", offsets),
        ("prompt_lens", prompt_len),
        ("remaining", remaining),
        ("temps", knobs[0]),
        ("top_ps", knobs[1]),
        ("rps", knobs[2]),
        # the budget folds into ``done`` as the decode loop folds it: a
        # row with no steps left enters pre-done
        ("done", remaining <= 0),
    ):
        out[key] = carry[key].at[r].set(value, mode="drop")
    return out


@functools.lru_cache(maxsize=None)
def _state_install_program():
    """``(row, state, r) -> state``: one row's recurrent state written into
    a row bucket's (models/ssm.py ``install_state_row``), the bucket donated
    where the stepped carry is (argument 1). One jitted function for every
    session, made at first use: the donation rule asks the backend."""
    from .jax_engine import _stepped_donation

    return jax.jit(
        lambda row, state, r: install_state_row(state, r, row),
        **_stepped_donation(),
    )


@jax.jit
def _park_table_row(table, r, page):
    """``table`` with every entry of row ``r`` pointing at ``page``.
    Jitted with ``r`` and ``page`` traced: ONE executable per table
    shape serves every slot, and the session compiles it at open
    (``_compile_slice``) — a retirement happens inside a decode slice,
    where a first-use compile would stall every resident row."""
    return table.at[r].set(page)


def _slab_bytes(slab) -> int:
    """Host bytes of a (possibly dict-leafed) swapped slab."""
    if isinstance(slab, dict):
        return sum(_slab_bytes(v) for v in slab.values())
    return int(slab.nbytes)


class _PendingJoin:
    """One joiner mid-chunked-prefill: the reserved slot, the private
    solo cache the chunks accumulate into, and the cursor over the
    token-budgeted chunk list. Holds its paged pages from ``join_begin``
    (reserved against concurrent joiners) until commit installs them or
    abort frees them. With a shared-prefix hit, ``hit_tokens`` leading
    positions were SEEDED instead of computed (the chunk list starts at
    the divergence) and the first ``shared_pages`` entries of ``pages``
    are read-only mappings of the prefix store's pool pages (one
    ``pool.share`` reference each — ``pool.free`` on abort/retire drops
    exactly that reference)."""

    __slots__ = (
        "request", "slot", "ids", "chunks", "next_chunk", "cache_len",
        "k_cache", "v_cache", "presence", "logits", "pages",
        "prefill_s", "t0", "hit_tokens", "shared_pages",
        "draft_k", "draft_v", "draft_chunks", "draft_next", "draft_ids",
        "resume", "resume_mode",
        "attr_wall", "attr_J", "attr_J_low", "attr_J_high",
    )

    def __init__(
        self, request, slot, ids, chunks, cache_len,
        k_cache, v_cache, presence, pages,
        hit_tokens=0, shared_pages=0,
    ):
        self.request = request
        self.slot = slot
        self.ids: List[int] = ids
        self.chunks: List[tuple] = chunks
        self.next_chunk = 0
        self.cache_len = cache_len
        self.k_cache = k_cache
        self.v_cache = v_cache
        self.presence = presence
        self.logits = None
        self.pages: List[int] = pages
        self.prefill_s = 0.0  # sum of chunk walls (not the interleaved span)
        # slice-attribution account of the chunk walls/Joules billed to
        # this joiner so far (ISSUE 20) — transferred onto the _Row at
        # commit, folded into _attr_dropped on abort
        self.attr_wall = 0.0
        self.attr_J = 0.0
        self.attr_J_low = 0.0
        self.attr_J_high = 0.0
        self.t0 = time.monotonic()
        self.hit_tokens = hit_tokens
        self.shared_pages = shared_pages
        # Speculative sessions (ISSUE 9): the joiner's DRAFT prefill
        # rides the same chunked machinery — a private draft cache and
        # its own chunk cursor over the FULL prompt (a shared-prefix hit
        # seeds only the TARGET cache; the draft is cheap enough to
        # recompute, and its chunks interleave like the target's).
        self.draft_k = None
        self.draft_v = None
        self.draft_chunks: List[tuple] = []
        self.draft_next = 0
        # the token ids the draft chunks prefill over — the prompt for
        # a fresh joiner, prompt + generated-so-far for a recompute
        # resume (None: fall back to ``ids``)
        self.draft_ids: Optional[List[int]] = None
        # Preemption resume (ISSUE 11): when set, this pending is a
        # RESUME riding the chunked-join machinery — ``resume`` is the
        # PreemptedRow and ``resume_mode`` how commit restores the KV
        # ("swap": scatter the host blob, zero chunks; "recompute": the
        # chunk list re-prefills prompt + generated-so-far).
        self.resume: "Optional[PreemptedRow]" = None
        self.resume_mode: Optional[str] = None

    @property
    def total_chunks(self) -> int:
        return len(self.chunks) + len(self.draft_chunks)


class PreemptedRow:
    """Everything needed to resume a mid-flight row that was retired by
    :meth:`SteppedDecodeSession.preempt` (ISSUE 11): the exact host copy
    of the row's control state (last token, rng key, presence, offsets,
    remaining budget) plus — under the ``swap`` policy — its KV payload
    (pool-page blob / contiguous row slab / stacked side-cache row).
    Shared CoW prefix pages are never swapped: their indices are
    recorded (``shared_pages``) so resume re-shares them from the ENGINE
    prefix store, falling back to full recompute when the store has
    moved on (spill with different pages, eviction) in the meantime."""

    __slots__ = (
        "request", "ids", "generated", "prompt_len", "offsets",
        "remaining", "rng", "presence", "use_top_p", "use_rp",
        "streamed", "t0", "t1", "policy", "paged", "stacked",
        "blob", "side_blob", "cache_blob", "draft_blob", "draft_offset",
        "shared_pages", "n_own_pages", "host_bytes", "discharged",
        "attr_wall", "attr_J", "attr_J_low", "attr_J_high",
        "attr_slices", "attr_wasted_J",
    )

    def __init__(self, request, ids, generated, prompt_len) -> None:
        self.request = request
        self.ids: List[int] = list(ids)
        self.generated: List[int] = list(generated)
        self.prompt_len = prompt_len
        self.offsets = 0
        self.remaining = 0
        self.rng = None
        self.presence = None
        self.use_top_p = False
        self.use_rp = False
        self.streamed = 0
        self.t0 = 0.0
        self.t1 = 0.0
        self.policy = "swap"
        self.paged = False
        self.stacked = False
        self.blob = None  # paged_kv.PageSwapBlob of the OWN pages
        self.side_blob = None  # stacked side-cache row (k, v) host slabs
        self.cache_blob = None  # contiguous row slab (k, v) host slabs
        # speculative row (ISSUE 16): the draft cache's row slabs +
        # draft offset under swap policy (model/cross sources; ngram
        # rebuilds its history from ids+generated instead)
        self.draft_blob = None
        self.draft_offset = 0
        self.shared_pages: List[int] = []  # leading shared page indices
        self.n_own_pages = 0
        self.host_bytes = 0
        self.discharged = False  # swap ledger already settled
        # slice-attribution account captured at preempt (ISSUE 20) —
        # restored onto the re-seated row so attributed wall/Joules
        # survive the park; the scheduler mirrors the victim's swap/
        # migration waste charge into attr_wasted_J
        self.attr_wall = 0.0
        self.attr_J = 0.0
        self.attr_J_low = 0.0
        self.attr_J_high = 0.0
        self.attr_slices = 0
        self.attr_wasted_J = 0.0


class _Row:
    """Host-side record of one live session row."""

    __slots__ = (
        "request", "s_real", "generated", "budget", "t0", "t1",
        "t_decode0", "pages", "streamed", "shared",
        "attr_wall", "attr_J", "attr_J_low", "attr_J_high",
        "attr_slices", "attr_wasted_J", "side0",
    )

    def __init__(
        self, request, s_real, first, budget, t0, t1, t_decode0,
        pages=None, shared=0,
    ):
        self.request = request
        self.s_real = s_real
        self.generated: List[int] = [first]
        self.budget = budget  # decode-loop steps (max_new_tokens - 1)
        self.t0 = t0
        self.t1 = t1
        self.t_decode0 = t_decode0
        self.pages: List[int] = pages or []
        # egress cursor: tokens already handed out via stream_deltas()
        self.streamed = 0
        # leading table-row pages mapped read-only from the prefix store
        # (preemption releases these instead of swapping them)
        self.shared = shared
        # slice-attribution account (ISSUE 20): this row's token-share
        # of every decode slice's wall and modelled Joules (plus its
        # join chunks), accumulated across preempt/resume and closed
        # out into extras["energy_model"] at retirement. attr_wasted_J
        # mirrors waste ALREADY on the wasted-energy ledger that this
        # row caused (fully-rejected draft rounds, its own swap /
        # migration) — informational, never double-counted into attr_J.
        self.attr_wall = 0.0
        self.attr_J = 0.0
        self.attr_J_low = 0.0
        self.attr_J_high = 0.0
        self.attr_slices = 0
        self.attr_wasted_J = 0.0
        # the side column its next decode step writes is side0 +
        # len(generated) - 1 (a stacked session's side caches)
        self.side0 = 0


def _carry_leaf(key: str) -> property:
    """Expose one carry-pytree leaf as a session attribute: reads and
    writes go to ``self.carry[key]``, so the host's eager per-row
    updates (cancels, table parks, a speculative row's draft state)
    mutate the SAME pytree the jitted slice step and the jitted row
    install (``_row_program``) take and return (and, on accelerator
    backends, donate) — there is exactly one device state, and it
    round-trips both programs without a host copy."""

    def get(self):
        return self.carry[key]

    def set_(self, value):
        self.carry[key] = value

    return property(get, set_)


class SteppedDecodeSession:
    """One resumable batched decode (see the module docstring).

    The device state is ONE explicit pytree, ``self.carry`` — the full
    loop carry of the stepped decode fns (row-control leaves plus the
    KV payload: batch cache, or page pool + table + side caches). The
    slice step is jitted over that pytree with the carry DONATED on
    accelerator backends (jax_engine._stepped_donation), and
    on a sharded engine (parallel/tp.py) every leaf declares a
    NamedSharding — KV payload sharded over heads when they divide the
    mesh, row-control replicated — so the same scheduler loop is
    device-count-agnostic: the carry never bounces through host memory
    between slices, on one chip or eight.

    The host state is one :class:`_Row` per live slot. ``rows[r] is
    None`` marks a free slot (never admitted, or retired) — free slots
    ride along pre-done, replicating row 0's offsets so their masked
    attention never softmaxes an empty row, exactly the monolithic
    paths' padding-row convention.
    """

    # every device leaf lives in self.carry; these names stay usable as
    # plain attributes so per-row update sites read naturally
    tokens = _carry_leaf("tokens")
    offsets = _carry_leaf("offsets")
    prompt_lens = _carry_leaf("prompt_lens")
    remaining = _carry_leaf("remaining")
    temps = _carry_leaf("temps")
    top_ps = _carry_leaf("top_ps")
    rps = _carry_leaf("rps")
    presence = _carry_leaf("presence")
    done = _carry_leaf("done")
    rngs = _carry_leaf("rngs")
    k_cache = _carry_leaf("k_cache")
    v_cache = _carry_leaf("v_cache")
    table = _carry_leaf("table")
    side_k = _carry_leaf("side_k")
    side_v = _carry_leaf("side_v")

    def __init__(self, engine, model: str, top_k: int) -> None:
        self.engine = engine
        self.model = model
        self.top_k = top_k
        self.closed = False
        self.last_slice_compiled = False
        self.last_slice_wait_s: Optional[float] = None
        # an expert model's routing counts of the last slice (MOE_COUNT_NAMES
        # -> int), which the scheduler puts on its ``sched.slice`` span;
        # None for a model without an expert layer
        self.last_slice_moe: Optional[Dict[str, int]] = None
        # a stacked paged session's ``side_block_steps`` of the last slice
        # (the side caches' (row, column block) pairs its steps read) and
        # ``pool_pages_read`` (the pool pages a layer of its steps read,
        # summed over the steps), for the scheduler's ``sched.slice``
        # span; None for any other session
        self.last_slice_side: Optional[Dict[str, int]] = None
        # a state-space model's ``state_row_steps`` of the last slice, for
        # the same span; None for a model without state-space layers
        self.last_slice_state: Optional[Dict[str, int]] = None
        # a model with a lightning indexer's ``index_blocks_seen`` /
        # ``index_blocks_kept`` of the last slice, for ``sched.slice``
        self.last_slice_index: Optional[Dict[str, int]] = None
        # runs of the row program (``_row_program``): one a join or a
        # resume, plus the open's compiling runs
        self.row_programs = 0
        # weight-LRU eviction pins held by this session (set at the END
        # of a successful open; released exactly once by close)
        self._session_pins: List[str] = []
        self.paged = bool(engine.paged_kv)
        self.carry: Dict[str, Any] = {}
        self.rows: List[Optional[_Row]] = []
        # tp×dp row sharding (ISSUE 19): >1 when the mesh has a dp axis
        # AND the bucket/page counts divide it (set by _open_paged; the
        # carry shardings apply the same divisibility fallback). Rows
        # map to contiguous shard blocks — r // (b_bucket / dp) — the
        # exact split NamedSharding P("dp") makes on the row dim, so a
        # shard-tagged page allocation keeps a row's pages device-local.
        self.dp_shards = 1
        # Speculative draft-verify mode (ISSUE 9): `spec` is the ACTIVE
        # config ({draft, k, dcfg, floor}) or None; `spec_info` survives
        # an adaptive fallback so retiring rows still report their
        # pre-fallback stats. Paged spec rows verify NATIVELY (ISSUE
        # 10): candidates live in the side caches / scratch leaves, the
        # pool stays page-resident, and a row bills exactly the
        # plain-decode page count — the former 2k+2 `spec_slack` page
        # billing is gone.
        self.spec: Optional[Dict[str, Any]] = None
        self.spec_info: Optional[Dict[str, Any]] = None
        self.spec_fallback = False
        self.spec_draft_len = 0
        self.spec_margin = 0
        # host-side cumulative per-slot spec counters (mirrors of the
        # carry leaves, refreshed each slice) + the rolling acceptance
        # window the fallback policy reads
        self._spec_host: Dict[str, List[int]] = {}
        self._spec_recent: "List[tuple]" = []
        # per-row cross-model draft Joules already billed as wasted
        self._spec_draft_wasted: List[float] = []
        # slot -> _PendingJoin: chunked joiners mid-prefill. A reserved
        # slot is not free (free_slots/can_join account for it) and not
        # live (the decode loop's done-mask still marks it done).
        self._pending: Dict[int, _PendingJoin] = {}
        self.use_top_p = False
        self.use_rp = False
        # Persistent cross-session prefix store (ISSUE 14,
        # engine/radix_store.py): ENGINE-owned — this session consults
        # and publishes to it, but never owns it; hits survive the
        # session, its pool, and scheduler restarts. None when
        # engine.prefix_share is off — every prefix code path below
        # guards on it, so the off configuration is bit-for-bit the
        # pre-ISSUE-7 session.
        self.store = getattr(engine, "prefix_store", None)
        # Streaming egress (serve/stream.py): the scheduler flips
        # stream_tokens on while any live ticket streams; only then do
        # retirements buffer their tail deltas for the next
        # stream_deltas() drain (bounded by the session's rows).
        self.stream_tokens = False
        self._stream_tail: List[tuple] = []
        # Preemption swap ledger (ISSUE 11): bytes/rows of THIS
        # session's victims currently parked in host memory. The global
        # gauges (llm_swap_host_bytes/rows) move through _swap_account
        # only, so after every victim resumed or was discarded they are
        # back exactly at their idle values.
        self._swap_bytes = 0
        self._swap_rows = 0
        # Slice-attribution books (ISSUE 20): everything ever billed to
        # rows of this session (slices + join chunks) and the accounts
        # of rows that left without retiring (cancel / abort / close).
        # Conservation invariant — live accounts + retired close-outs +
        # dropped == totals, within float summation error — is what the
        # tenant tests pin. Empty dicts when telemetry is off: the
        # billing sites are all _obs_enabled()-gated.
        self._attr_totals = {"wall": 0.0, "J": 0.0, "J_low": 0.0, "J_high": 0.0}
        self._attr_dropped = {"wall": 0.0, "J": 0.0, "J_low": 0.0, "J_high": 0.0}

    # -- construction ---------------------------------------------------------
    @classmethod
    def open(
        cls,
        engine,
        requests: "list[GenerationRequest]",
        reserve_rows: Optional[int] = None,
        slice_steps: Optional[int] = None,
        spec_accept_floor: Optional[float] = None,
        spec_override=None,
    ) -> "SteppedDecodeSession":
        from .jax_engine import (
            BATCH_BUCKETS,
            DECODE_SLICE_STEPS,
            GEN_BUCKETS,
            _bucket,
        )

        if not requests:
            raise ValueError("decode_open needs at least one request")
        models = {r.model for r in requests}
        if len(models) > 1:
            raise ValueError(f"one model per session, got {sorted(models)}")
        top_ks = {r.top_k for r in requests}
        if len(top_ks) > 1:
            raise ValueError(f"one top_k per session, got {sorted(top_ks)}")
        model = requests[0].model
        engine.load_model(model)
        self = cls(engine, model, requests[0].top_k)
        self.cfg = engine._models[model].cfg
        self.tok = engine._tokenizer_for(model)
        all_ids = [self.tok.encode(r.prompt) for r in requests]
        n = len(requests)
        self.b_bucket = _bucket(
            max(n, int(reserve_rows or 0)), BATCH_BUCKETS
        )
        self.g_bucket = _bucket(
            max(r.max_new_tokens for r in requests), GEN_BUCKETS
        )
        self.slice_bucket = max(1, int(slice_steps or DECODE_SLICE_STEPS))
        # Speculative mode probe BEFORE cache sizing: the contiguous
        # target cache carries the rounds-overshoot margin (and a
        # stacked paged session its side-column overshoot) only when
        # the session will actually speculate.
        self._init_spec(
            requests, all_ids, spec_accept_floor, spec_override
        )
        # the engine's stepped-compute context covers every compile/run
        # in the open (TP: the int4 Pallas kernel has no GSPMD rule —
        # same guard its generate paths apply)
        if not self.paged:
            engine._refuse_contiguous_rows(model, self.cfg)
        with engine._stepped_compute_ctx():
            if self.paged:
                self._open_paged(requests, all_ids)
            else:
                self._open_contiguous(requests, all_ids)
            if self.spec is not None:
                if self.spec["draft"] is not None:
                    self._open_draft(all_ids)
                else:
                    self._open_ngram(all_ids)
            # one explicit placement for the assembled carry: identity on
            # a single device; on a mesh every leaf is device_put to the
            # sharding the jitted slice step declares (heads-sharded KV
            # payload, replicated row control, a speculating session's
            # draft cache by the DRAFT model's heads), so the session
            # starts committed to the SPMD layout it will keep
            self.carry = engine._place_carry(
                self.cfg, self.carry, draft_cfg=self._draft_cfg()
            )
            if self.paged:
                self.pool.k = self.carry["pool_k"]
                self.pool.v = self.carry["pool_v"]
        self._compile_slice()
        # Eviction guard (ISSUE 15): the open SUCCEEDED — pin this
        # session's weights (target + live draft) against the weight
        # LRU until close(). Registered last so a failed open never
        # leaks a pin that would immortalise the model.
        self._session_pins = [self.model]
        if self.spec is not None and self.spec["draft"] is not None:
            # model/cross sources pin the DRAFT weights too — for a
            # cross-model source this is the eviction guard that keeps
            # another lane's resident model alive while it drafts here
            self._session_pins.append(self.spec["draft"])
        opened = getattr(engine, "_session_opened", None)
        if opened is not None:
            for name in self._session_pins:
                opened(name)
        return self

    # -- speculative draft-verify mode (ISSUE 9) -------------------------------
    def _draft_cfg(self):
        return self.spec["dcfg"] if self.spec is not None else None

    def _init_spec(
        self,
        requests: "list[GenerationRequest]",
        all_ids: "list[list[int]]",
        spec_accept_floor: Optional[float],
        spec_override=None,
    ) -> None:
        """Decide whether this session runs draft-verify: the engine has
        a :class:`~.speculative.DraftSpec` for the model (or the caller
        forced one via ``spec_override``), every opening row is eligible
        (greedy or sampled within ``spec_temperature_max`` — ISSUE 16),
        the source isn't blocked by its recent-acceptance memory, and —
        model/cross sources — the draft is co-resident with a matching
        vocabulary and its contiguous cache fits its max_seq_len. The
        ngram source has no draft model: its "cache" is an int32
        history buffer sized like the draft cache would be. Any miss
        serves the session PLAIN — configuring a draft must never fail
        a request plain decode would serve (the solo path's rule)."""
        from ..runner import term
        from .jax_engine import _prompt_alloc, _spec_margin

        eng = self.engine
        spec = (
            spec_override
            if spec_override is not None
            else eng._resolve_spec(self.model)
        )
        if spec is None:
            return
        if not all(eng._spec_eligible(r) for r in requests):
            return
        source, draft, k = spec.source, spec.draft, spec.k
        floor = (
            eng.spec_accept_floor
            if spec_accept_floor is None
            else float(spec_accept_floor)
        )
        if spec_override is None and eng._spec_source_blocked(
            source, draft, floor
        ):
            # the source's recent sessions all fell back under the
            # floor — skip arming (the consult decays the memory, so a
            # later session re-probes)
            return
        margin = _spec_margin(k)
        draft_len = (
            max(_prompt_alloc(max(len(i), 1)) for i in all_ids)
            + self.g_bucket
            + margin
        )
        dcfg = None
        if draft is not None:
            eng.load_model(draft)
            if self.model not in eng._models:
                # the draft's load may have evicted the target
                eng.load_model(self.model)
            if self.model not in eng._models or draft not in eng._models:
                term.log_warn(
                    f"speculative session: {self.model} and {draft} "
                    "cannot be co-resident; serving the session without "
                    "the draft"
                )
                return
            dcfg = eng._models[draft].cfg
            if dcfg.vocab_size != self.cfg.vocab_size:
                term.log_warn(
                    f"speculative session: draft {draft} vocab "
                    f"{dcfg.vocab_size} != target vocab "
                    f"{self.cfg.vocab_size}; serving plain"
                )
                return
            if draft_len > dcfg.max_seq_len:
                return
        self.spec = {
            "source": source, "draft": draft, "k": k, "dcfg": dcfg,
            "floor": floor,
            # the CONFIGURED draft length: the adaptive policy (ISSUE
            # 19) shrinks "k" below it under a failing acceptance window
            # and restores toward it on recovery, but never above —
            # every open-time allocation (scratch width, side-cache
            # overshoot, contiguous margin) was sized from k0
            "k0": k,
        }
        self.spec_info = {"draft_model": draft, "k": k, "source": source}
        self.spec_draft_len = draft_len
        self.spec_margin = margin

    def _disable_spec_at_open(self) -> None:
        """Back out of spec mode DURING open (cache would not fit): the
        session never speculated, so no fallback event/counters."""
        self.spec = None
        self.spec_info = None
        self.spec_margin = 0
        self.spec_draft_len = 0

    def _open_draft(self, all_ids: "list[list[int]]") -> None:
        """Prefill the draft over every opening row's prompt and
        assemble the contiguous batch draft cache into the carry (the
        draft never pages and never quantizes — it is tiny). Padding
        rows replicate row 0 and ride pre-done like everywhere else."""
        eng = self.engine
        draft = self.spec["draft"]
        rows_k, rows_v = [], []
        for ids in all_ids:
            _, dk, dv = eng._run_prefill(draft, ids, self.spec_draft_len)
            rows_k.append(dk)
            rows_v.append(dv)
        pad = self.b_bucket - len(all_ids)
        self.carry["draft_k"] = jnp.concatenate(
            rows_k + [rows_k[0]] * pad, axis=1
        )
        self.carry["draft_v"] = jnp.concatenate(
            rows_v + [rows_v[0]] * pad, axis=1
        )
        offs = [len(i) for i in all_ids] + [len(all_ids[0])] * pad
        self.carry["draft_offsets"] = jnp.asarray(offs, dtype=jnp.int32)
        self._open_spec_counters()

    def _open_ngram(self, all_ids: "list[list[int]]") -> None:
        """Assemble the prompt-lookup source's carry state (ISSUE 16):
        one int32 history row per slot — prompt ids followed by the
        row's first sampled token, capacity ``spec_draft_len`` (the
        prompt bucket + generation budget + rounds-overshoot margin, so
        every append the accept lane can produce fits). Padding rows
        replicate row 0 like everywhere else. Zero extra weights, zero
        extra forwards — this is the whole open cost."""
        import numpy as np

        h = self.spec_draft_len
        hist = np.zeros((self.b_bucket, h), dtype=np.int32)
        hlen = np.zeros((self.b_bucket,), dtype=np.int32)
        rows = [
            ids + [row.generated[0]]
            for ids, row in zip(all_ids, self.rows)
        ]
        rows += [rows[0]] * (self.b_bucket - len(all_ids))
        for r, full in enumerate(rows):
            hist[r, : len(full)] = full
            hlen[r] = len(full)
        self.carry["ngram_hist"] = jnp.asarray(hist)
        self.carry["ngram_len"] = jnp.asarray(hlen)
        self._open_spec_counters()

    def _open_spec_counters(self) -> None:
        b = self.b_bucket
        for key in (
            "spec_rounds", "spec_accepted", "spec_drafted",
            "spec_rejected",
        ):
            self.carry[key] = jnp.zeros((b,), jnp.int32)
        self._spec_host = {
            "rounds": [0] * b, "accepted": [0] * b, "drafted": [0] * b,
            "rejected": [0] * b,
        }
        # per-row cross-model draft Joules billed to the wasted-energy
        # ledger so far (host-side; retiring rows report theirs)
        self._spec_draft_wasted = [0.0] * b

    def _set_ngram_row(self, r: int, full: "List[int]") -> None:
        """(Re)build one slot's n-gram history row from its known token
        stream (join commit, preemption resume) — the host always knows
        prompt + generated exactly, so the matcher's state needs no
        device capture to survive a round trip."""
        h = int(self.carry["ngram_hist"].shape[1])
        full = full[:h]
        row = jnp.zeros((h,), jnp.int32).at[: len(full)].set(
            jnp.asarray(full, jnp.int32)
        )
        self.carry["ngram_hist"] = self.carry["ngram_hist"].at[r].set(row)
        self.carry["ngram_len"] = (
            self.carry["ngram_len"].at[r].set(len(full))
        )

    def _open_common(self, requests, states, pad: int) -> None:
        """Assemble the per-row device arrays shared by both cache
        layouts (free slots replicate row 0 and enter pre-done)."""
        rep = [states[0]] * pad
        self.tokens = jnp.concatenate(
            [st["first"] for st in states] + [s["first"] for s in rep]
        )
        self.rngs = jnp.stack(
            [st["rng"] for st in states] + [s["rng"] for s in rep]
        )
        self.presence = jnp.concatenate(
            [st["presence"] for st in states]
            + [s["presence"] for s in rep],
            axis=0,
        )
        offs = [st["s_real"] for st in states] + [
            states[0]["s_real"]
        ] * pad
        self.offsets = jnp.asarray(offs, dtype=jnp.int32)
        self.prompt_lens = jnp.asarray(offs, dtype=jnp.int32)
        self.remaining = jnp.asarray(
            [r.max_new_tokens - 1 for r in requests] + [0] * pad,
            dtype=jnp.int32,
        )
        self.temps = jnp.asarray(
            [r.temperature for r in requests]
            + [requests[0].temperature] * pad,
            dtype=jnp.float32,
        )
        self.top_ps = jnp.asarray(
            [self._row_top_p(r) for r in requests]
            + [self._row_top_p(requests[0])] * pad,
            dtype=jnp.float32,
        )
        self.rps = jnp.asarray(
            [r.repeat_penalty for r in requests]
            + [requests[0].repeat_penalty] * pad,
            dtype=jnp.float32,
        )
        # a max_new_tokens=1 row has no decode steps: it enters done and
        # retires on the first step call with just its prefill token
        self.done = jnp.asarray(
            [r.max_new_tokens <= 1 for r in requests] + [True] * pad
        )
        self.use_top_p = any(st["use_top_p"] for st in states)
        self.use_rp = any(st["use_rp"] for st in states)
        t_open = time.monotonic()
        self.rows = [
            _Row(
                r,
                st["s_real"],
                int(st["first"][0]),
                r.max_new_tokens - 1,
                st["t0"],
                st["t1"],
                t_open,
            )
            for r, st in zip(requests, states)
        ] + [None] * pad

    @staticmethod
    def _row_top_p(r: GenerationRequest) -> float:
        # sentinel 2.0 ≡ filter provably off for that row (the batch
        # paths' convention — see _generate_batch_chunk)
        return r.top_p if r.top_p < 1.0 else 2.0

    def _open_contiguous(self, requests, all_ids) -> None:
        from .jax_engine import _prompt_alloc

        eng = self.engine
        cfg = self.cfg
        # dp row sharding engages on the contiguous layout whenever the
        # bucket divides the dp axis — the exact rule the carry
        # shardings apply to the batch-position leaves. No pool here, so
        # no page-count condition and no per-shard parking.
        dp = int(getattr(eng, "_dp_shards", lambda: 1)())
        self.dp_shards = (
            dp if dp > 1 and self.b_bucket % dp == 0 else 1
        )
        s_buckets = [_prompt_alloc(max(len(i), 1)) for i in all_ids]
        # spec sessions carry the rounds-overshoot margin (verify writes
        # up to offset+k; _spec_margin rounds 2k+2 to the lane tile) —
        # when that margin would blow max_seq_len, serve plain instead
        self.cache_len = max(s_buckets) + self.g_bucket + self.spec_margin
        if self.spec is not None and self.cache_len > cfg.max_seq_len:
            self._disable_spec_at_open()
            self.cache_len = max(s_buckets) + self.g_bucket
        if self.cache_len > cfg.max_seq_len:
            raise ValueError(
                f"{self.model}: session cache {self.cache_len} exceeds "
                f"max_seq_len {cfg.max_seq_len}"
            )
        states = eng._batch_states(
            requests, all_ids, [self.cache_len] * len(requests)
        )
        n = len(states)
        pad = self.b_bucket - n
        k_cache = jnp.concatenate(
            [st["k_cache"] for st in states]
            + [states[0]["k_cache"]] * pad,
            axis=1,
        )
        v_cache = jnp.concatenate(
            [st["v_cache"] for st in states]
            + [states[0]["v_cache"]] * pad,
            axis=1,
        )
        if eng.kv_quantize:
            k_cache, v_cache = eng._quantize_batch_cache(
                self.model, k_cache, v_cache
            )
        self.k_cache, self.v_cache = k_cache, v_cache
        self._open_common(requests, states, pad)
        if self.store is not None:
            self.store.attach_pool(self.model, None)
            for ids, st, row in zip(all_ids, states, self.rows):
                self._publish_prefix(
                    ids, st["k_cache"], st["v_cache"], row.pages
                )

    def _open_paged(self, requests, all_ids) -> None:
        import numpy as np

        from .jax_engine import _prompt_alloc
        from .paged_kv import (
            PagePool,
            _paginate,
            pad_to_pool,
            pool_widths,
            quantize_chunks,
            scatter_pages,
            side_rows,
        )

        eng = self.engine
        cfg = self.cfg
        page = eng.page_size
        for r, ids in zip(requests, all_ids):
            if len(ids) + r.max_new_tokens > cfg.max_seq_len:
                raise ValueError(
                    f"{self.model}: prompt {len(ids)} + generation "
                    f"{r.max_new_tokens} exceeds max_seq_len "
                    f"{cfg.max_seq_len}"
                )
        # Stacked-hybrid mode follows kernel presence alone (ISSUE 10):
        # the multi-query parts kernel scores a speculating row's k+1
        # candidate positions in one page-streaming pass, so spec
        # sessions ride the stacked layout like everyone else —
        # candidates land in the side caches (sized with a k-column
        # overshoot below), the pool stays prompt-only and page-resident
        # during verify, and no slack pages exist. Kernel-less sessions
        # verify against the gathered pool with candidates in the small
        # scratch carry leaves, committed through the table only after
        # acceptance.
        self.stacked = eng._paged_decode_attention(cfg) is not None
        self.quantized = bool(eng.kv_quantize)
        self.page_size = page
        cache_lens = [_prompt_alloc(max(len(i), 1)) for i in all_ids]
        if cfg.state_layers:
            states = self._open_state(requests, all_ids, cache_lens)
        else:
            states = eng._batch_states(requests, all_ids, cache_lens)
        n = len(states)
        pad = self.b_bucket - n
        rows_pages = [
            self._pages_needed(st["s_real"], r.max_new_tokens)
            for st, r in zip(states, requests)
        ]
        # ×2 page and table-width headroom over the initial fleet so
        # mid-flight joins have pages to allocate and slots to fit —
        # without it a lone anchor's session could never admit anyone
        dp = int(getattr(eng, "_dp_shards", lambda: 1)())
        total = sum(rows_pages) + max(1, dp)  # + per-shard parking pages
        n_pages = _pow2_at_least(2 * total, 4)
        # dp engages only when the bucket AND page count divide it —
        # the stepped_carry_shardings divisibility fallback, mirrored
        # here so the host allocator and the GSPMD placement agree
        self.dp_shards = (
            dp
            if dp > 1 and self.b_bucket % dp == 0 and n_pages % dp == 0
            else 1
        )
        self.jmax = _pow2_at_least(2 * max(rows_pages))
        self.pool_widths = pool_widths(cfg, self.stacked)
        self.pool = PagePool.create(
            n_layers=cfg.cache_layers,
            n_pages=n_pages,
            n_kv_heads=cfg.cache_heads,
            d_head=self.pool_widths[0],
            d_head_v=self.pool_widths[1],
            page_size=page,
            dtype=eng.dtype,
            quantized=self.quantized,
            dp_shards=self.dp_shards,
        )
        # Retired/free slots park their table rows here: a done row
        # re-writes one frozen (page, slot) each step (legacy mode), and
        # that write must never land on pages a live or future row owns.
        # One parking page PER dp shard so a parked table row keeps
        # pointing at pages on the shard that owns the row.
        self.parking_pages = [
            self.pool.alloc(1, shard=s)[0] for s in range(self.dp_shards)
        ]
        self.parking = self.parking_pages[0]
        table_np = np.empty((self.b_bucket, self.jmax), dtype=np.int32)
        for r in range(self.b_bucket):
            table_np[r, :] = self._parking_for(r)
        chunk_dest: List[int] = []
        chunks_k, chunks_v = [], []
        row_pages: List[List[int]] = []
        for r, (st, need) in enumerate(zip(states, rows_pages)):
            pages = self.pool.alloc(need, shard=self._row_shard(r))
            row_pages.append(pages)
            table_np[r, :need] = pages
            n_prompt_pages = -(-st["s_real"] // page)
            chunk_dest.extend(pages[:n_prompt_pages])
            ck = _paginate(st["k_cache"][:, 0], st["s_real"], page)
            cv = _paginate(st["v_cache"][:, 0], st["s_real"], page)
            ck, cv = pad_to_pool(ck, cv, self.pool_widths)
            chunks_k.append(ck)
            chunks_v.append(cv)
        all_k = (
            chunks_k[0] if len(chunks_k) == 1 else jnp.concatenate(chunks_k)
        )
        all_v = (
            chunks_v[0] if len(chunks_v) == 1 else jnp.concatenate(chunks_v)
        )
        if self.quantized:
            all_k, all_v = quantize_chunks(all_k, all_v)
        self.pool.k, self.pool.v = scatter_pages(
            self.pool.k,
            self.pool.v,
            jnp.asarray(chunk_dest, jnp.int32),
            all_k,
            all_v,
        )
        # placement happens once, over the WHOLE carry, at the end of
        # open() (_place_carry) — the pool/table join it below
        self.table = jnp.asarray(table_np)
        if self.stacked:
            # a speculating session's verify writes candidates at
            # write_pos..write_pos+k — up to k columns past the last
            # budgeted token — so its side caches carry a k-column
            # overshoot (bytes, not pages: the slack-free billing point)
            side_cols = self.g_bucket + (
                self.spec["k"] if self.spec is not None else 0
            )
            lead = (
                cfg.cache_layers, self.b_bucket, cfg.cache_heads, side_cols,
            )
            self.side_k = side_rows(
                lead, cfg.cache_k_width, eng.dtype, self.quantized
            )
            self.side_v = side_rows(
                lead, cfg.cache_v_width, eng.dtype, self.quantized
            )
        else:
            # two DISTINCT scalar sentinels: the carry is donated on
            # accelerators, and XLA rejects one buffer donated twice
            self.side_k = jnp.int32(0)
            self.side_v = jnp.int32(0)
        self._open_common(requests, states, pad)
        for row, pages in zip(self.rows, row_pages):
            row.pages = pages
        if self.store is not None:
            self.store.attach_pool(self.model, self.pool)
            for ids, st, row in zip(all_ids, states, self.rows):
                self._publish_prefix(
                    ids, st["k_cache"], st["v_cache"], row.pages
                )
        if self.spec is not None and not self.stacked:
            # kernel-less native verify (ISSUE 10): the per-round
            # candidate K/V live in these small scratch leaves — a mini
            # contiguous cache [L, B, Hkv, k+1, Dh] so the TP payload
            # sharding rule applies verbatim — and only the committed
            # prefix reaches the pool, through one post-acceptance
            # scatter per round
            sshape = (
                cfg.cache_layers, self.b_bucket, cfg.cache_heads,
                self.spec["k"] + 1, cfg.cache_k_width,
            )
            for key in ("scratch_k", "scratch_v"):
                if self.quantized:
                    self.carry[key] = {
                        "q": jnp.zeros(sshape, jnp.int8),
                        "s": jnp.zeros(sshape[:-1], jnp.float32),
                    }
                else:
                    self.carry[key] = jnp.zeros(sshape, dtype=eng.dtype)
        if cfg.n_experts:
            # the slice's routing counts (engine/jax_engine.py,
            # _paged_batch_decode_step_fn), fetched with its tokens
            self.carry["moe_counts"] = jnp.zeros(
                (len(MOE_COUNT_NAMES),), jnp.int32
            )
        # pool payload enters the carry last (scatters above built it);
        # PagePool.k/v stay views of the same arrays (re-synced after
        # placement and after every slice)
        self.carry["pool_k"] = self.pool.k
        self.carry["pool_v"] = self.pool.v

    # rows of the opening fleet that prefill together where the model has
    # state-space layers: a row's state is megabytes a layer, the bucket's
    # is allocated first and each group's rows move into it before the
    # next group prefills, so the open never holds the fleet's states twice
    STATE_OPEN_ROWS = 4

    def _open_state(self, requests, all_ids, cache_lens):
        """The opening fleet's prefill for a model with state-space
        layers: the recurrent state of the whole row bucket, zero, enters
        the carry as ``ssm`` (models/ssm.py), the fleet prefills
        ``STATE_OPEN_ROWS`` rows at a time, and each row's state is
        written into its slot where the bucket lies (the carry's donation
        rule). Returns ``_batch_states``'s states with the attention
        layers' cache alone in ``k_cache``."""
        eng = self.engine
        ssm = init_state(self.cfg, self.b_bucket, eng.dtype)
        install = _state_install_program()
        states = []
        for lo in range(0, len(requests), self.STATE_OPEN_ROWS):
            hi = lo + self.STATE_OPEN_ROWS
            for st in eng._batch_states(
                requests[lo:hi], all_ids[lo:hi], cache_lens[lo:hi]
            ):
                record = st["k_cache"]
                st["k_cache"] = record["kv"]
                ssm = install(record["ssm"], ssm, jnp.int32(len(states)))
                states.append(st)
        self.carry["ssm"] = ssm
        return states

    @property
    def state_counts(self) -> Dict[str, int]:
        """``sched.slice``'s ``state_rows`` (rows whose recurrent state
        the session HOLDS: the whole row bucket, live or not) and
        ``state_bytes`` (``state_bytes_per_row`` times those rows). What
        a slice's steps read and wrote of it is ``state_row_steps``
        (``last_slice_state``). Empty for a model without state-space
        layers."""
        if not self.cfg.state_layers:
            return {}
        return {
            "state_rows": len(self.rows),
            "state_bytes": state_bytes(self.carry["ssm"]),
        }

    def state_impl(self) -> str:
        """What a decode step does with the recurrent state (the rule of
        the stack's recurrent mixer, models/ssm.py ``ssm_step_impl`` or
        models/kda.py ``kda_step_impl``, asked under the context the step
        was traced in): ``"pallas-live"`` or ``"xla-bucket"``."""
        rule = kda_step_impl if self.cfg.state_mixer == MIXER_KDA else ssm_step_impl
        with self.engine._stepped_compute_ctx():
            return rule(self.cfg, self.carry["ssm"], 1)

    def _index_block_counts(self, live: "List[int]", ran: "List[int]") -> Dict[str, int]:
        """``sched.slice``'s ``index_blocks_seen`` / ``index_blocks_kept``
        of a model with a lightning indexer, host arithmetic from the rows'
        positions: a step at position ``t`` sees ``t // kpool`` whole
        blocks and its tail and keeps ``min(t // kpool, index_topk)`` and
        the tail (models/transformer.py ``_index_keep``), summed over the
        live rows' steps and the indexed layers."""
        kp, topk = self.cfg.index_kpool, self.cfg.index_topk
        seen = kept = 0
        for r, n in zip(live, ran):
            row = self.rows[r]
            t0 = row.s_real + len(row.generated) - 1
            for t in range(t0, t0 + n):
                seen += t // kp + 1
                kept += min(t // kp, topk) + 1
        layers = self.cfg.attention_layers
        return {"index_blocks_seen": layers * seen, "index_blocks_kept": layers * kept}

    def side_impl(self) -> str:
        """What a decode step does with the side caches
        (models/transformer.py ``side_impl``, asked under the context the
        step was traced in, for the tokens its step runs a row):
        ``"pallas-live"`` or ``"xla"``."""
        tokens = 1 if self.spec is None else self.spec["k"] + 1
        with self.engine._stepped_compute_ctx():
            return side_impl(self.cfg, self.side_k, tokens)

    def pool_impl(self) -> str:
        """What a decode step does with the pool
        (:meth:`JaxEngine._paged_decode_impl`, asked under the context the
        step was traced in, of the pool the step reads): ``"pallas-pool"``,
        ``"xla-pool"``, ``"xla"``, ``"pallas"`` or ``"gather"``."""
        with self.engine._stepped_compute_ctx():
            return self.engine._paged_decode_impl(
                self.cfg, len(self.rows), self.jmax, self.store is not None,
                self.pool.k,
            )

    def _pool_pages_read(self, live: "List[int]") -> int:
        """The pool pages a layer of one step of the slice reads, host
        arithmetic at dispatch: under ``pallas-pool`` the held pages (the
        live rows' prompt pages, and the parking page of each shard that
        a parked row, whose length is stale, claims), under ``xla`` the
        table's pages of every bucket row, else the pool's size."""
        impl = self.pool_impl()
        if impl == "pallas-pool":
            parked = {
                self._parking_for(r) for r, row in enumerate(self.rows)
                if row is None
            }
            return len(parked) + sum(
                -(-self.rows[r].s_real // self.page_size) for r in live
            )
        if impl == "xla":
            return len(self.rows) * self.jmax
        return self.pool.n_pages

    def _side_block_steps(self, live: "List[int]", ran: "List[int]") -> int:
        """The (row, column block) pairs of the side caches that a slice's
        steps read, host arithmetic: under ``pallas-live`` each live row's
        ``write_pos // block + 1`` at each of its steps (its ``n_row``),
        under ``xla`` every bucket row's every block at every step run."""
        side = self.side_k["q"] if isinstance(self.side_k, dict) else self.side_k
        cols, width = side.shape[3:]
        block = side_block(cols, width)
        if self.side_impl() != "pallas-live":
            return len(self.rows) * (cols // block) * max(ran, default=0)

        def upto(w: int) -> int:  # sum of x // block + 1 for x < w
            q, rem = divmod(w, block)
            return block * q * (q + 1) // 2 + rem * (q + 1)

        total = 0
        for r, n in zip(live, ran):
            row = self.rows[r]
            w0 = row.side0 + len(row.generated) - 1
            total += upto(w0 + n) - upto(w0)
        return total

    def _row_shard(self, r: int) -> int:
        """dp shard owning slot ``r`` — the contiguous-block split
        ``NamedSharding(P("dp"))`` makes on the row dim."""
        if self.dp_shards <= 1:
            return 0
        return min(
            r // (self.b_bucket // self.dp_shards), self.dp_shards - 1
        )

    def _parking_for(self, r: int) -> int:
        """Parking page on slot ``r``'s own dp shard."""
        pages = getattr(self, "parking_pages", None)
        if not pages:
            return self.parking
        return pages[self._row_shard(r)]

    def _park_row(self, r: int) -> None:
        """Point slot ``r``'s table row at its parking page."""
        self.table = _park_table_row(self.table, r, self._parking_for(r))

    def _pages_needed(self, s_real: int, max_new_tokens: int) -> int:
        from .paged_kv import pages_pinned

        return pages_pinned(
            s_real, max_new_tokens, self.page_size, self.stacked
        )

    # -- persistent prefix store (engine/radix_store.py, ISSUE 14) -------------
    def _publish_prefix(self, ids, k_cache, v_cache, pages) -> None:
        """Publish a completed prompt prefill to the ENGINE store: full
        page-aligned prompt pages (safe to share — prefill wrote them
        and neither layout writes a FULL prompt page again: decode
        appends land at positions >= s_real) plus the bf16 seed slab
        the divergent-tail prefill of a future sharer attends through.
        ``k_cache`` is the row's PRE-QUANTIZATION private cache
        ``[L, 1, Hkv, S, D]``.

        Publication is UNCAPPED (ISSUE 14): a joiner's own divergent-
        tail pages are adopted by the store too, so a second-generation
        sharer maps the first sharer's tail pages read-only. The store
        holds one refcount per adopted page — they outlive the
        publisher's retirement and return to the pool only at store
        spill/eviction (or pool detach at close)."""
        s_real = len(ids)
        if self.store is None or s_real < 2:
            return
        k_seed = k_cache[:, 0, :, :s_real]
        v_seed = v_cache[:, 0, :, :s_real]
        if self.paged:
            full = s_real // self.page_size
            self.store.publish(
                self.model, ids, k_seed, v_seed, pages[:full], self.pool
            )
        else:
            self.store.publish(self.model, ids, k_seed, v_seed, None, None)

    def _prefix_hit(self, ids: "List[int]"):
        """Longest usable store hit for ``ids`` as a PLAN dict —
        ``{"common", "hbm_lead", "restore_nodes", "restore_pages",
        "full_pages"}`` — with ``common`` capped so at least one tail
        token is still computed (prefill must produce last-position
        logits), or None. Side-effect free — ``can_join`` probes it;
        ``join_begin`` executes it (restores + page mapping)."""
        if self.store is None:
            return None
        common = self.store.match_len(self.model, ids)
        common = min(common, len(ids) - 1)
        if common <= 0:
            return None
        plan = {
            "common": common,
            "hbm_lead": [],
            "restore_nodes": [],
            "restore_pages": 0,
            "full_pages": 0,
        }
        if self.paged:
            plan.update(self.store.page_plan(self.model, ids, common))
        return plan

    # -- introspection --------------------------------------------------------
    @property
    def active(self) -> int:
        return sum(1 for r in self.rows if r is not None)

    @property
    def ctx_tokens(self) -> int:
        """Sum of the live rows' contexts (prompt + generated so far):
        what a decode slice's attention has to read, as the program
        counts it."""
        return sum(
            row.s_real + len(row.generated)
            for row in self.rows
            if row is not None
        )

    @property
    def pool_page_counts(self) -> Dict[str, int]:
        """``sched.slice``'s ``pool_pages`` (the pool's size: what a
        step that reads the pool in place reads) and
        ``pool_pages_owned`` (pages on the live rows' page lists: what
        somebody needed of it). Empty for a session without a pool."""
        if not self.paged:
            return {}
        return {
            "pool_pages": self.pool.n_pages,
            "pool_pages_owned": sum(
                len(row.pages) for row in self.rows if row is not None
            ),
        }

    @property
    def free_slots(self) -> int:
        """Slots open to a new joiner: not live AND not reserved by a
        pending chunked join."""
        return sum(
            1
            for r, row in enumerate(self.rows)
            if row is None and r not in self._pending
        )

    @property
    def pending_joins(self) -> int:
        return len(self._pending)

    def debug_state(self) -> Dict[str, Any]:
        """Live JSON-able snapshot for ``GET /debug/state``: per-slot row
        state (ages, token counts, budgets, page holdings), pending
        joiners' chunk progress, and (paged) pool occupancy. Read-only
        and lock-free — a racing slice costs a stale field, nothing
        more."""
        now = time.monotonic()
        state: Dict[str, Any] = {
            "model": self.model,
            "closed": self.closed,
            "paged": self.paged,
            "b_bucket": len(self.rows),
            "slice_steps": self.slice_bucket,
            "active": self.active,
            "free_slots": self.free_slots,
            "pending_joins": self.pending_joins,
            "rows": [
                {
                    "slot": r,
                    "prompt_tokens": row.s_real,
                    "generated_tokens": len(row.generated),
                    "budget": row.budget,
                    "age_s": round(now - row.t0, 4),
                    "pages": len(row.pages),
                    **(
                        {
                            "spec_rounds": int(
                                self._spec_host["rounds"][r]
                            ),
                            "spec_accepted": int(
                                self._spec_host["accepted"][r]
                            ),
                            "verify_mode": self._verify_mode(),
                        }
                        if self.spec_info is not None and self._spec_host
                        else {}
                    ),
                }
                for r, row in enumerate(self.rows)
                if row is not None
            ],
            "pending": [
                {
                    "slot": pj.slot,
                    "prompt_tokens": len(pj.ids),
                    "chunks_done": pj.next_chunk,
                    "total_chunks": pj.total_chunks,
                    "age_s": round(now - pj.t0, 4),
                    "pages": len(pj.pages),
                }
                for pj in self._pending.values()
            ],
        }
        if self.cfg.residual_streams > 1 or len(self.cfg.layer_runs) > 1:
            # a stack that is not one run of one-stream layers
            state["stack"] = {
                "residual_streams": self.cfg.residual_streams,
                "layer_runs": [count for _, _, count in self.cfg.layer_runs],
            }
        if self.cfg.state_layers:
            # layers of two kinds of state: a recurrent state a row beside
            # the attention layers' pages (behind a lightning indexer:
            # sparse latent attention)
            state["stack"]["layer_kinds"] = {
                self.cfg.state_mixer: self.cfg.state_layers,
                "sparse_latent" if self.cfg.index_topk else "attention":
                self.cfg.attention_layers,
            }
            ssm = self.carry["ssm"]
            state["state"] = {
                "bytes_per_row": state_bytes(ssm) // len(self.rows),
                "rows": len(self.rows),
                "dtype": str(ssm["s"].dtype),
                "conv_dtype": str(ssm["conv"].dtype),
                "impl": self.state_impl(),
            }
        if self.cfg.n_experts:
            # what a decode step's grouped expert FFN compiled to at this
            # session's row bucket and the model's expert leaves (asked
            # under the context the step was traced in)
            with self.engine._stepped_compute_ctx():
                impl = moe_impl(
                    self.cfg, self.engine.dtype, len(self.rows),
                    self.engine._models[self.model].params,
                )
            state["moe"] = {
                "impl": impl,
                "block_rows": moe_block_rows(self.cfg, len(self.rows)),
            }
        if self.spec_info is not None:
            recent_acc = sum(a for a, _ in self._spec_recent)
            recent_drafted = sum(d for _, d in self._spec_recent)
            state["spec"] = {
                "active": self.spec is not None,
                "draft_model": self.spec_info["draft_model"],
                "source": self.spec_info.get("source", "model"),
                "k": self.spec_info["k"],
                "fallback": self.spec_fallback,
                "verify_mode": self._verify_mode(),
                "scratch_bytes": self._spec_scratch_bytes(),
                "accept_floor": (
                    self.spec["floor"] if self.spec is not None else None
                ),
                "acceptance_recent": (
                    round(recent_acc / recent_drafted, 4)
                    if recent_drafted
                    else None
                ),
                "rounds_total": sum(self._spec_host.get("rounds", [])),
                "accepted_total": sum(self._spec_host.get("accepted", [])),
                "drafted_total": sum(self._spec_host.get("drafted", [])),
                "rejected_total": sum(self._spec_host.get("rejected", [])),
            }
        # preemption swap accounting (ISSUE 11): what THIS session has
        # parked in host memory right now — returns to zeros once every
        # victim resumed or was discarded
        state["swap"] = {
            "host_rows": self._swap_rows,
            "host_bytes": self._swap_bytes,
        }
        if self.paged:
            state["pool"] = self.pool.debug_state()
            # what this session's decode step compiled its attention to
            # at its static shapes (row bucket × page-table width) and
            # with or without a prefix store to share pages through
            state["attention"] = {
                "table_width": self.jmax,
                "impl": self.pool_impl(),
                # and the side caches (a stacked session's)
                "side": self.side_impl(),
            }
            if self.cfg.index_topk:
                state["attention"]["index"] = {
                    "topk": self.cfg.index_topk, "kpool": self.cfg.index_kpool,
                }
        mesh_info = getattr(self.engine, "mesh_info", None)
        info = mesh_info() if callable(mesh_info) else None
        if info is not None:
            # sharded session: report the mesh and what each device
            # actually holds — per-device KV payload bytes come from the
            # carry leaves' own committed shardings (shard_shape), so a
            # placement regression shows up here, not just in step time
            state["mesh"] = dict(info)
            state["mesh"]["per_device_kv_bytes"] = self._per_device_kv_bytes()
            if self.paged:
                state["pool"]["per_device"] = {
                    "bytes": self._per_device_kv_bytes(pool_only=True),
                    "pages": self.pool.n_pages,
                    "occupancy": state["pool"]["occupancy"],
                }
        if self.store is not None:
            # the ENGINE store's snapshot (node count, depth, bytes by
            # tier) — session-independent state, surfaced here so one
            # /debug/state probe shows what a joiner could hit RIGHT NOW
            state["prefix_store"] = self.store.debug_state()
        return state

    def _verify_mode(self) -> str:
        """How this session's speculative verify touches the target KV
        (ISSUE 10): ``native`` on paged sessions — candidates live in a
        carry-side scratch (the side caches' overshoot columns in
        stacked mode, the dedicated scratch leaves otherwise), the pool
        stays page-resident and no slack pages are billed; ``legacy``
        is the contiguous carry-resident verify (no pages exist to
        bill, so nothing changed there)."""
        return "native" if self.paged else "legacy"

    def _spec_scratch_bytes(self) -> int:
        """Bytes of carry-side verify scratch this session holds: the
        dedicated ``scratch_k/v`` leaves (kernel-less native mode), or
        the side caches' k overshoot columns (stacked native mode —
        the candidates' landing strip past the generation budget).
        Contiguous sessions report 0 (the verify writes land inside the
        carry cache's existing margin)."""
        total = 0
        for key in ("scratch_k", "scratch_v"):
            leaf = self.carry.get(key)
            if leaf is None:
                continue
            parts = leaf.values() if isinstance(leaf, dict) else (leaf,)
            total += sum(int(arr.nbytes) for arr in parts)
        if (
            total == 0
            and self.paged
            and self.spec is not None
            and self.stacked
        ):
            k = self.spec["k"]
            for key in ("side_k", "side_v"):
                leaf = self.carry.get(key)
                parts = (
                    leaf.values() if isinstance(leaf, dict) else (leaf,)
                )
                for arr in parts:
                    if getattr(arr, "ndim", 0) == 0:
                        continue
                    cols = arr.shape[3]  # [L,B,Hkv,Tgen(,D)]
                    total += int(arr.nbytes) * k // max(cols, 1)
        return total

    def _per_device_kv_bytes(self, pool_only: bool = False) -> int:
        """Bytes of KV payload ONE device holds under the carry's
        committed shardings (pool + side caches, or the contiguous batch
        cache). Head-sharded layouts report 1/tp of the total; a
        replicated fallback (heads don't divide the mesh) reports the
        full payload — the honest number either way."""
        keys = (
            ("pool_k", "pool_v") if pool_only
            else ("pool_k", "pool_v", "side_k", "side_v")
            if self.paged
            else ("k_cache", "v_cache")
        )
        if not pool_only:
            # a speculating session's draft cache is KV payload too, as
            # are the native verify's scratch leaves (ISSUE 10) and the
            # recurrent state of a model with state-space layers
            keys = keys + (
                "draft_k", "draft_v", "scratch_k", "scratch_v", "ssm",
            )
        total = 0
        for key in keys:
            leaf = self.carry.get(key)
            if leaf is None:
                continue
            parts = leaf.values() if isinstance(leaf, dict) else (leaf,)
            for arr in parts:
                if getattr(arr, "ndim", 0) == 0:
                    continue  # legacy-mode side sentinel
                shard = arr.sharding.shard_shape(arr.shape)
                n = 1
                for d in shard:
                    n *= d
                total += n * arr.dtype.itemsize
        return int(total)

    # -- the compiled slice ---------------------------------------------------
    def _run_slice(self, n_real: int):
        """Run the compiled slice step for at most ``n_real`` steps and
        return ``(out_tokens, n_row)``. ONE carry in, ONE carry out: on
        accelerators the step donates the input pytree (its buffers
        alias the output's), and on a sharded engine it runs under
        explicit in/out shardings — the whole per-iteration state stays
        resident on the device(s)."""
        eng = self.engine
        params = eng._models[self.model].params
        with eng._stepped_compute_ctx():
            if self.spec is not None:
                decode = eng._spec_batch_decode_step_fn(
                    self.model, self.spec["draft"], self.spec["k"],
                    self.slice_bucket, self.paged,
                    self.paged and self.quantized,
                    stacked=self.paged and self.stacked,
                    carry=self.carry,
                    source=self.spec["source"],
                    top_k=self.top_k,
                    use_top_p=self.use_top_p,
                )
                dparams = (
                    eng._models[self.spec["draft"]].params
                    if self.spec["draft"] is not None
                    else None
                )
                params = (params, dparams)
            elif self.paged:
                decode = eng._paged_batch_decode_step_fn(
                    self.model, self.slice_bucket, self.top_k,
                    self.use_top_p, self.use_rp, self.stacked,
                    self.quantized, self.store is not None,
                    carry=self.carry,
                )
            else:
                decode = eng._batch_decode_step_fn(
                    self.model, self.slice_bucket, self.top_k,
                    self.use_top_p, self.use_rp, carry=self.carry,
                )
            out, n_row, self.carry = decode(
                params, self.carry, jnp.int32(n_real)
            )
        if self.paged:
            self.pool.k = self.carry["pool_k"]
            self.pool.v = self.carry["pool_v"]
        return out, n_row

    def _compile_slice(self) -> None:
        """Compile, at open, what a decode slice runs — so no slice ever
        pays it. A slice stalls every resident row, while the open has
        only its own rows waiting (and already pays the prefill's
        compile). The slice step is run for ZERO steps: the loop body
        never executes and the carry comes back unchanged, but the
        executable now sits in the jit's own cache, keyed by exactly the
        shapes and placements the real slices pass. The retirement's
        table update is run on a discarded copy, re-placed the way
        ``_recommit_carry`` will. What still compiles inside a slice is
        a step whose STATIC knobs changed mid-session (a joiner's first
        top-p / repeat-penalty row, a speculative fallback): rows are
        resident then, and the scheduler reports it as an anomaly.

        A join runs between two slices and stalls the same rows, so the
        row install (``_row_program``) compiles here too: run on slot
        ``b_bucket`` with every page outside the pool it writes nothing
        and hands the carry back, and its executable is keyed by the
        shapes a real join passes. Those are the carry's and the private
        cache's: a paged joiner's cache is as long as its prompt's
        bucketed end, and the open compiles the ends its own fleet's
        prompts would have as joiners (traffic resembles itself); a
        joiner of another bucket compiles its install beside the
        prefill chunk of that bucket, once."""
        self._run_slice(0)
        if self.paged:
            parked = _park_table_row(self.table, 0, self._parking_for(0))
            jax.device_put(parked, self.table.sharding)
        for cache_len in self._install_lengths():
            self._run_row_program(
                len(self.rows),
                first_token=0,
                rng=jax.random.PRNGKey(0),
                presence=jnp.zeros((1, self.cfg.vocab_size), dtype=bool),
                offsets=0,
                prompt_len=0,
                remaining=0,
                knobs=(0.0, 0.0, 0.0),
                pages=[],
                cache=self._private_cache(cache_len) + (0, 0),
            )

    def _install_lengths(self) -> "List[int]":
        """Private-cache lengths whose install the open compiles: the
        one length a contiguous session's joiners have, or the bucketed
        ends of the opening prompts under the default join chunk."""
        from .jax_engine import (
            JOIN_PREFILL_CHUNK_TOKENS,
            PROMPT_BUCKETS,
            _floor_bucket,
            _prompt_chunks,
        )

        if not self.paged:
            return [self.cache_len]
        chunk = _floor_bucket(JOIN_PREFILL_CHUNK_TOKENS, PROMPT_BUCKETS)
        ends = set()
        for row in self.rows:
            if row is not None:
                start, bucket = _prompt_chunks(max(row.s_real, 1), chunk)[-1]
                ends.add(start + bucket)
        return sorted(ends)

    def _private_cache(self, cache_len: int):
        """An empty solo cache of ``cache_len`` positions, placed as the
        prefill chunks expect it: what a join's chunks accumulate into."""
        eng = self.engine
        k_cache, v_cache = eng._models[self.model].init_cache(
            1, cache_len, dtype=eng.dtype
        )
        return eng._place_cache(k_cache, v_cache, self.cfg)

    # -- stepping -------------------------------------------------------------
    def step(self, max_steps: Optional[int] = None) -> List[GenerationResult]:
        """Run one bounded decode slice; returns the results of every row
        that retired during it (EOS or budget exhaustion). The caller
        regains control after at most ``slice_bucket`` steps."""
        from .jax_engine import _to_host_list

        if self.closed:
            raise RuntimeError("session is closed")
        live = [r for r, row in enumerate(self.rows) if row is not None]
        if not live:
            return []
        n_real = min(max_steps or self.slice_bucket, self.slice_bucket)
        compiles0 = compile_count()
        t1 = time.monotonic()
        # the slice's four phases as spans (PERF.md §3): enqueue, the
        # device's run, the three fetches, the host's bookkeeping
        with TRACER.span("session.slice.dispatch"):
            out, n_row = self._run_slice(n_real)
        with TRACER.span("session.slice.wait") as wait_span:
            out = jax.block_until_ready(out)
        # what the scheduler's rule for a long pass reads: the seconds
        # this slice sat waiting for the device (None: telemetry off)
        self.last_slice_wait_s = None if wait_span is None else wait_span.dur_s
        with TRACER.span("session.slice.fetch"):
            out_host = _to_host_list(out)
            n_row_host = _to_host_list(n_row)
            done_host = _to_host_list(self.done)
            ran = [int(n_row_host[r]) for r in live]
            if "moe_counts" in self.carry and self.spec is None:
                self.last_slice_moe = dict(
                    zip(MOE_COUNT_NAMES, _to_host_list(self.carry["moe_counts"])),
                    moe_steps=max(ran), moe_tokens=sum(ran),
                )
            if self.paged and self.stacked:
                self.last_slice_side = {
                    "side_block_steps": self._side_block_steps(live, ran),
                    # summed over the steps the slice ran
                    "pool_pages_read": self._pool_pages_read(live)
                    * max(ran, default=0),
                }
            if self.cfg.state_layers:
                # the (row, step) pairs whose state the slice read and
                # wrote: a live row's steps (its token_mask was true for
                # exactly n_row of them), or every bucket row's
                self.last_slice_state = {
                    "state_row_steps": sum(ran)
                    if self.state_impl() == "pallas-live"
                    else len(self.rows) * max(ran)
                }
            if self.cfg.index_topk:
                self.last_slice_index = self._index_block_counts(live, ran)
            # spec accounting BEFORE retirement: the deltas feed the
            # llm_spec_* families and may flip the session to plain decode
            # (adaptive fallback) — retiring rows read the refreshed host
            # counters for their extras either way
            spec_rounds_slice = (
                self._spec_after_slice(live)
                if self.spec is not None
                else None
            )
        t2 = time.monotonic()
        with TRACER.span("session.slice.account"):
            return self._account_slice(
                live, out_host, n_row_host, done_host, spec_rounds_slice,
                t1, t2, compiles0,
            )

    def _account_slice(
        self, live, out_host, n_row_host, done_host, spec_rounds_slice,
        t1: float, t2: float, compiles0: int,
    ) -> List[GenerationResult]:
        """The host's bookkeeping after a slice's tokens are fetched
        (``session.slice.account``): energy/wall attribution, token
        hand-out, retirement, goodput and decode-window telemetry."""
        eng = self.engine
        counts = {r: int(n_row_host[r]) for r in live}
        slice_tokens = sum(counts.values())
        slice_steps = max(counts.values(), default=0)
        if spec_rounds_slice is not None:
            # in spec mode the device executed ROUNDS, not per-token
            # steps: one target weight-read per round for up to k+1
            # tokens — that is the amortization the whole mode exists
            # for, and what tokens-per-target-step measures
            slice_steps = spec_rounds_slice
        if _obs_enabled() and slice_tokens:
            # attribute BEFORE retiring: rows completing this slice must
            # carry their share of ITS wall/Joules into their close-out
            try:
                self._attr_slice(counts, t2 - t1, max(1, slice_steps))
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        retired: List[GenerationResult] = []
        for r in live:
            cnt = counts[r]
            if cnt:
                self.rows[r].generated.extend(out_host[r][:cnt])
            if done_host[r]:
                retired.append(self._retire(r, t2))
        # Goodput accounting (obs/detect.py): the compiled slice steps
        # EVERY bucket row — live, finished-mid-slice, and padding rows
        # alike — so the device executed ~slice_steps × b_bucket row-
        # steps while only the live rows' sampled tokens were useful.
        # Completed rows credit the numerator at retirement (_retire).
        observe_slice_tokens(slice_steps, len(self.rows))
        if _obs_enabled() and slice_tokens:
            try:
                eng._observe_decode_window(
                    t1, t2, slice_tokens, slice_steps, rows=len(live)
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        # open() compiled everything a slice runs (_compile_slice); a
        # slice that compiled anyway — or loaded from the persistent
        # cache — stalled its resident rows, and the scheduler reports it
        self.last_slice_compiled = compile_count() != compiles0
        return retired

    # -- slice-level energy & wall attribution (ISSUE 20) ----------------------
    def _attr_slice(
        self, counts: "Dict[int, int]", wall: float, steps: int
    ) -> None:
        """Split ONE decode slice's wall clock and modelled Joules across
        the resident rows by token share: a row that sampled ``cnt`` of
        the slice's ``slice_tokens`` tokens owns ``cnt/slice_tokens`` of
        both — the idle tail a narrow batch pays distributes over the
        rows that were actually decoding, which is exactly the marginal-
        cost question ("who pays the Joules for this content"). The
        energy model prices the slice at each row's own context length
        (``slice_window_stats``), so the split also reflects KV-stream
        asymmetry in aggregate. Telemetry-only: the caller gates on
        ``_obs_enabled()`` and wraps in try/except."""
        slice_tokens = sum(counts.values())
        if not slice_tokens or wall <= 0:
            return
        pairs = []
        for r, cnt in counts.items():
            row = self.rows[r]
            pairs.append((row.s_real + len(row.generated), cnt))
        est = self.engine._slice_energy(
            self.model, self.cfg, pairs, wall, steps
        )
        j = jl = jh = 0.0
        if est is not None:
            j, jl, jh = est["J"], est["J_low"], est["J_high"]
        tot = self._attr_totals
        tot["wall"] += wall
        tot["J"] += j
        tot["J_low"] += jl
        tot["J_high"] += jh
        for r, cnt in counts.items():
            if not cnt:
                continue
            row = self.rows[r]
            share = cnt / slice_tokens
            row.attr_wall += wall * share
            row.attr_J += j * share
            row.attr_J_low += jl * share
            row.attr_J_high += jh * share
            row.attr_slices += 1

    def _attr_chunk(
        self, pending: _PendingJoin, ctx: int, new: int, wall: float
    ) -> None:
        """Bill one join-prefill chunk's wall/Joules to the JOINER (the
        in-flight rows stall for it, but the work is the joiner's — the
        same single-owner rule as the slice split). ``ctx`` is the chunk
        start offset, ``new`` its real token count."""
        if wall <= 0 or new <= 0:
            return
        est = self.engine._slice_energy(
            self.model, self.cfg, [(ctx, new)], wall, 1
        )
        j = jl = jh = 0.0
        if est is not None:
            j, jl, jh = est["J"], est["J_low"], est["J_high"]
        tot = self._attr_totals
        tot["wall"] += wall
        tot["J"] += j
        tot["J_low"] += jl
        tot["J_high"] += jh
        pending.attr_wall += wall
        pending.attr_J += j
        pending.attr_J_low += jl
        pending.attr_J_high += jh

    def _attr_drop(self, account) -> None:
        """Move a departing account (cancelled row, aborted pending,
        close-abandoned row) into the dropped books so the session-level
        conservation invariant stays exact."""
        d = self._attr_dropped
        d["wall"] += account.attr_wall
        d["J"] += account.attr_J
        d["J_low"] += account.attr_J_low
        d["J_high"] += account.attr_J_high

    def _close_out_energy(
        self, r: int, row: _Row, extras: Dict[str, Any], gen_tokens: int
    ) -> None:
        """Stamp the retiring row's accumulated attribution into
        ``extras["energy_model"]`` (``window="slice"`` — the continuous-
        path twin of the window/solo paths' shapes), publish it to the
        llm_request_* energy families, and refresh the engine's live
        J/token feed (the figure least-joules routing and auto model
        policy read). 9-decimal rounding keeps the wire compact while
        conserving against the session books well inside 1e-6."""
        from ..obs.energy import observe_estimate

        eng = self.engine
        j, jl, jh = row.attr_J, row.attr_J_low, row.attr_J_high
        jpt = j / gen_tokens if gen_tokens else 0.0
        wasted = row.attr_wasted_J
        if self._spec_draft_wasted and self._spec_draft_wasted[r]:
            wasted += self._spec_draft_wasted[r]
        extras["energy_model"] = {
            "J": round(j, 9),
            "J_low": round(jl, 9),
            "J_high": round(jh, 9),
            "J_per_token": round(jpt, 9),
            "J_per_token_low": round(
                jl / gen_tokens if gen_tokens else 0.0, 9
            ),
            "J_per_token_high": round(
                jh / gen_tokens if gen_tokens else 0.0, 9
            ),
            "wall_attr_s": round(row.attr_wall, 9),
            "slices": row.attr_slices,
            "chip": eng.chip.device_kind,
            "window": "slice",
            **({"wasted_J": round(wasted, 9)} if wasted else {}),
        }
        observe_estimate(
            {
                "J": j,
                "J_per_token": jpt,
                "J_per_token_low": jl / gen_tokens if gen_tokens else None,
                "J_per_token_high": jh / gen_tokens if gen_tokens else None,
            }
        )
        if jpt > 0:
            # the least-joules routing feed (ISSUE 20 satellite): under
            # the continuous scheduler this is now refreshed on EVERY
            # retire, not only by the window/solo attribution paths
            eng.last_joules_per_token = jpt
            by_model = getattr(eng, "last_joules_per_token_by_model", None)
            if by_model is not None:
                by_model[self.model] = jpt

    def _spec_after_slice(self, live: "List[int]") -> int:
        """Refresh the host mirrors of the carry's cumulative spec
        counters, publish this slice's deltas (llm_spec_* + one
        ``spec_round`` flight event), feed the rolling-acceptance window
        and apply the adaptive fallback policy. Returns the number of
        draft-verify ROUNDS the compiled loop executed this slice (the
        max per-row round delta — every live row rides every loop
        iteration, so the max IS the iteration count)."""
        from .jax_engine import _to_host_list

        rounds = _to_host_list(self.carry["spec_rounds"])
        accepted = _to_host_list(self.carry["spec_accepted"])
        drafted = _to_host_list(self.carry["spec_drafted"])
        rejected = _to_host_list(self.carry["spec_rejected"])
        prev = self._spec_host
        rounds_delta = [a - b for a, b in zip(rounds, prev["rounds"])]
        rej_delta = [a - b for a, b in zip(rejected, prev["rejected"])]
        acc_delta = sum(accepted) - sum(prev["accepted"])
        drafted_delta = sum(drafted) - sum(prev["drafted"])
        self._spec_host = {
            "rounds": rounds, "accepted": accepted, "drafted": drafted,
            "rejected": rejected,
        }
        source = self.spec["source"]
        if source == "cross" and any(rej_delta):
            # Cross-model draft-waste billing (ISSUE 16): a FULLY
            # rejected round burned k draft forwards of ANOTHER lane's
            # model for zero emitted tokens — escalation-style, those
            # Joules land in the wasted-energy ledger under their own
            # cause, priced at the DRAFT model's live J/token when the
            # fleet hook provides it. Partially-accepted rounds bill
            # nothing: their draft work amortized into emitted tokens.
            try:
                from ..obs.energy import charge_wasted

                jpt_hook = getattr(self.engine, "spec_draft_jpt", None)
                jpt = jpt_hook(self.spec["draft"]) if jpt_hook else None
                for r, d in enumerate(rej_delta):
                    if d > 0:
                        joules = charge_wasted(
                            "draft",
                            tokens=float(d * self.spec["k"]),
                            jpt=jpt,
                        )
                        self._spec_draft_wasted[r] += joules
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        slice_rounds = max(
            [rounds_delta[r] for r in live] or [0]
        )
        if _obs_enabled() and slice_rounds:
            try:
                from ..obs.flight import EV_SPEC_ROUND, FLIGHT, trace_attrs
                from ..obs.metrics import observe_spec
                from ..obs.trace import TRACER

                observe_spec(
                    slice_rounds, acc_delta, drafted_delta, source=source,
                    rejected=sum(rej_delta) * self.spec["k"],
                )
                if self.paged:
                    # paged rounds verify NATIVELY (ISSUE 10): the
                    # counter makes the slack-free migration observable
                    from ..obs.metrics import SPEC_VERIFY_NATIVE_C

                    SPEC_VERIFY_NATIVE_C.inc(slice_rounds)
                FLIGHT.emit(
                    EV_SPEC_ROUND,
                    # the slice runs on the scheduler thread with the
                    # anchor's root attached — spec rounds join the
                    # fleet trace like every other flight event
                    **trace_attrs(TRACER.current()),
                    model=self.model,
                    draft=self.spec["draft"],
                    source=source,
                    k=self.spec["k"],
                    rounds=slice_rounds,
                    accepted=acc_delta,
                    drafted=drafted_delta,
                    acceptance=(
                        round(acc_delta / drafted_delta, 4)
                        if drafted_delta
                        else None
                    ),
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        # Adaptive policy: a rolling window of recent slices' (accepted,
        # drafted); once the window holds enough evidence (≥ 2 slices
        # and ≥ 2k drafts) and its acceptance sits below the floor,
        # speculation at THIS draft length is losing — every round paid
        # k draft steps + a k+1-wide verify for ~1 emitted token. The
        # session first SHRINKS k (halving toward 1, ISSUE 19): a
        # shorter draft has strictly higher per-token acceptance odds,
        # so a source in a rough patch keeps some speedup instead of
        # abandoning the armed draft outright. Full fallback is the
        # k=1-still-failing endgame. A recovered window (comfortably
        # above the floor — the +0.15 hysteresis band keeps the two
        # thresholds from oscillating) restores k toward the
        # configured k0, never past it (allocations were sized at k0).
        floor = self.spec["floor"]
        if floor > 0.0 and drafted_delta:
            self._spec_recent.append((acc_delta, drafted_delta))
            self._spec_recent = self._spec_recent[-4:]
            win_acc = sum(a for a, _ in self._spec_recent)
            win_drafted = sum(d for _, d in self._spec_recent)
            if (
                len(self._spec_recent) >= 2
                and win_drafted >= 2 * self.spec["k"]
            ):
                measured = win_acc / win_drafted
                if measured < floor:
                    if self.spec["k"] > 1:
                        self._spec_set_k(
                            max(1, self.spec["k"] // 2), measured
                        )
                    else:
                        self._spec_fall_back(measured)
                elif (
                    self.spec["k"] < self.spec["k0"]
                    and measured >= min(0.95, floor + 0.15)
                ):
                    self._spec_set_k(
                        min(self.spec["k0"], self.spec["k"] * 2),
                        measured,
                    )
        return slice_rounds

    def _spec_set_k(
        self, k_new: int, measured_acceptance: float
    ) -> None:
        """Move the session's live draft length (ISSUE 19 adaptive
        draft-k). The compiled slice step is keyed on k, so the next
        ``step()`` picks up (or compiles) the k_new variant; the
        acceptance window resets so the new length earns its own
        evidence. Parity is untouched — every k emits the target's own
        accept/resample stream, k only moves the speedup."""
        from ..runner import term

        k_old = int(self.spec["k"])
        k_new = int(k_new)
        if k_new == k_old:
            return
        self.spec["k"] = k_new
        if self.spec_info is not None:
            self.spec_info["k"] = k_new
        self._spec_recent = []
        if (
            self.paged
            and not self.stacked
            and self.carry.get("scratch_k") is not None
        ):
            # the kernel-less native verify's scratch leaves are shaped
            # [L,B,Hkv,k+1,Dh] and the compiled commit scatters the
            # WHOLE column dim — rebuild them at the new width
            # (contents are per-round transients: each round writes its
            # candidates before reading them, so zeros are correct) and
            # re-place the carry so the new leaves join the committed
            # SPMD layout
            cfg = self.cfg
            sshape = (
                cfg.cache_layers, self.b_bucket, cfg.cache_heads,
                k_new + 1, cfg.cache_k_width,
            )
            for key in ("scratch_k", "scratch_v"):
                if self.quantized:
                    self.carry[key] = {
                        "q": jnp.zeros(sshape, jnp.int8),
                        "s": jnp.zeros(sshape[:-1], jnp.float32),
                    }
                else:
                    self.carry[key] = jnp.zeros(
                        sshape, dtype=self.engine.dtype
                    )
            self._recommit_carry()
        direction = "down" if k_new < k_old else "up"
        source = self.spec["source"]
        term.log_warn(
            f"speculative session [{self.model}]: source {source} "
            f"acceptance {measured_acceptance:.2f} — draft length "
            f"k {k_old} -> {k_new} ({direction})"
        )
        if _obs_enabled():
            try:
                from ..obs.flight import EV_SPEC_K_ADAPT, FLIGHT
                from ..obs.metrics import SPEC_K_ADAPT_C

                SPEC_K_ADAPT_C.labels(
                    source=source, direction=direction
                ).inc()
                FLIGHT.emit(
                    EV_SPEC_K_ADAPT,
                    model=self.model,
                    source=source,
                    k_from=k_old,
                    k_to=k_new,
                    acceptance=round(measured_acceptance, 4),
                    floor=self.spec["floor"],
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass

    def _spec_fall_back(self, measured_acceptance: float) -> None:
        """Switch the session to plain decode mid-flight: drop the draft
        leaves from the carry (the row-control and target-KV leaves are
        shared between the two compiled step families, so tokens,
        offsets, budgets and done-masks carry over exactly — parity is
        preserved because both modes emit the target's greedy stream)
        and keep ``spec_info``/host stats for retiring rows' extras."""
        from ..runner import term

        for key in (
            "draft_k", "draft_v", "draft_offsets",
            "ngram_hist", "ngram_len",
            "spec_rounds", "spec_accepted", "spec_drafted",
            "spec_rejected", "scratch_k", "scratch_v",
        ):
            self.carry.pop(key, None)
        floor = self.spec["floor"]
        source = self.spec["source"]
        draft = self.spec["draft"]
        self.spec = None
        self.spec_fallback = True
        self._spec_recent = []
        self._recommit_carry()
        # feed the engine's per-source acceptance memory: enough
        # below-floor sessions and _init_spec stops arming this source
        # for a while (the adaptive window, learned per source — ngram
        # collapse must not gate model-draft sessions)
        feedback = getattr(self.engine, "_spec_source_feedback", None)
        if feedback is not None:
            feedback(source, draft, measured_acceptance)
        term.log_warn(
            f"speculative session [{self.model}]: source {source} "
            f"measured acceptance {measured_acceptance:.2f} < floor "
            f"{floor:g}; falling back to plain decode"
        )
        if _obs_enabled():
            try:
                from ..obs.flight import EV_SPEC_FALLBACK, FLIGHT
                from ..obs.metrics import SPEC_FALLBACK_C

                SPEC_FALLBACK_C.labels(source=source).inc()
                FLIGHT.emit(
                    EV_SPEC_FALLBACK,
                    model=self.model,
                    source=source,
                    acceptance=round(measured_acceptance, 4),
                    floor=floor,
                )
            except Exception:  # noqa: BLE001 — telemetry only
                pass

    def _retire(self, r: int, t2: float) -> GenerationResult:
        from .jax_engine import _apply_stop

        row = self.rows[r]
        req = row.request
        generated = row.generated
        eos = self.tok.eos_id
        reason = (
            "eos" if generated and generated[-1] == eos else "budget"
        )
        if req.stop_at_eos and eos in generated:
            generated = generated[: generated.index(eos)]
        text = self.tok.decode(generated)
        if req.stop:
            generated, text = _apply_stop(generated, text, self.tok, req.stop)
        extras: Dict[str, Any] = {"retire_reason": reason, "stepped": True}
        if self.spec_info is not None and self._spec_host:
            # per-row draft-verify attribution (ISSUE 9): the row's own
            # rounds/accepted/drafted from the host counter mirrors —
            # frozen at their pre-fallback values when the adaptive
            # policy switched the session to plain decode mid-flight
            extras["spec"] = {
                "rounds": int(self._spec_host["rounds"][r]),
                "accepted": int(self._spec_host["accepted"][r]),
                "drafted": int(self._spec_host["drafted"][r]),
                "rejected": int(
                    self._spec_host.get("rejected", [0] * len(self.rows))[r]
                ),
                "k": self.spec_info["k"],
                "draft_model": self.spec_info["draft_model"],
                "source": self.spec_info.get("source", "model"),
                "fallback": self.spec_fallback,
            }
            if self._spec_draft_wasted and self._spec_draft_wasted[r]:
                # cross-model drafting: Joules of ANOTHER lane's model
                # this row burned in fully-rejected rounds (already in
                # the wasted-energy ledger under cause="draft")
                extras["spec"]["draft_wasted_J"] = round(
                    self._spec_draft_wasted[r], 6
                )
        if _obs_enabled() and (row.attr_slices or row.attr_wall):
            try:
                self._close_out_energy(r, row, extras, len(generated))
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        result = GenerationResult(
            request=req,
            tokens=generated,
            text=text,
            prompt_tokens=row.s_real,
            generated_tokens=len(generated),
            prefill_s=row.t1 - row.t0,
            decode_s=t2 - row.t_decode0,
            total_s=t2 - row.t0,
            extras=extras,
        )
        # the row COMPLETED (eos/budget): its DECODE-LOOP tokens were
        # useful device work — the goodput numerator (the first token
        # came from prefill, outside the stepped denominator; rows
        # abandoned at close() never credit — wasted by definition)
        observe_retired_tokens(max(0, len(row.generated) - 1))
        if self.stream_tokens and row.streamed < len(generated):
            # buffer the retiring row's unstreamed tail (post-cut, so
            # concatenated deltas equal the final token list) for the
            # next stream_deltas() drain — the row record dies here
            tail = generated[row.streamed :]
            self._stream_tail.append((req, tail, self.tok.decode(tail)))
        if self.paged:
            # park the slot's table row FIRST: the dead row's frozen
            # write slot (legacy mode) must stop aliasing pages we are
            # about to hand back to the free list
            self._park_row(r)
            self.pool.free(row.pages)
            row.pages = []
            self._recommit_carry()
        self.rows[r] = None
        return result

    # -- streaming egress ------------------------------------------------------
    def stream_deltas(self) -> List[tuple]:
        """Each row's tokens generated since the previous call, as
        ``(request, tokens, text)`` triples — the producer feed of the
        per-request egress channels (serve/stream.py). Rows that retired
        since the last call contribute their buffered post-cut tail, so
        a fully-drained stream's concatenated deltas equal the final
        token list (stop-STRING cuts are the documented exception: they
        cut retroactively, and the final event's text is authoritative).
        EOS is clipped from live-row deltas when the row asked
        ``stop_at_eos`` — an EOS the result will not contain must not be
        streamed."""
        out: List[tuple] = list(self._stream_tail)
        self._stream_tail.clear()
        eos = self.tok.eos_id
        for row in self.rows:
            if row is None or len(row.generated) <= row.streamed:
                continue
            new = row.generated[row.streamed :]
            row.streamed = len(row.generated)
            if row.request.stop_at_eos and eos in new:
                new = new[: new.index(eos)]
            if new:
                out.append((row.request, new, self.tok.decode(new)))
        return out

    def cancel(self, request: GenerationRequest) -> bool:
        """Retire a live row NOW without completing it (client
        disconnect / deadline): the row leaves the done-mask bookkeeping
        as if it had finished — parked table row, pages back to the pool
        free-list mid-flight — but its partial stream is DISCARDED and
        its tokens never credit goodput (abandoned work is wasted by
        definition, same rule as close()). Returns False when the
        request has no live row (already retired — the race is benign).
        """
        for r, row in enumerate(self.rows):
            if row is None or row.request is not request:
                continue
            # same ordering discipline as _retire: mark the row done on
            # device (it rides along as a padding row from the next
            # slice), park its table row FIRST, then free its pages
            self.done = self.done.at[r].set(True)
            self.remaining = self.remaining.at[r].set(0)
            if self.paged:
                self._park_row(r)
                self.pool.free(row.pages)
                row.pages = []
            # the cancelled row's attributed wall/Joules never close out
            # — settle them into the dropped books (ISSUE 20)
            self._attr_drop(row)
            self.rows[r] = None
            self._recommit_carry()
            return True
        return False

    # -- mid-flight preemption (ISSUE 11) --------------------------------------
    def _refuse_bundles(self, mechanism: str) -> None:
        """A latent cache's rows have no swap bundle yet: preemption
        (swap or recompute) and the migration that rides it are refused
        by name (``resume_begin`` is where a migrated-in row arrives)."""
        if self.cfg.state_layers:
            raise UnsupportedMechanism(
                mechanism, self.model,
                "a row's recurrent state has no snapshot to swap, "
                "recompute from or migrate",
            )
        if self.cfg.latent or self.cfg.blocks_per_layer > 1:
            raise UnsupportedMechanism(
                mechanism, self.model,
                "a row's latent cache rows have no swap / migrate bundle",
            )

    def _row_slab(self, cache, r: int):
        """Host copy of one row of a (possibly dict-leafed) batch cache,
        the batch dim kept singleton so ``_set_row`` restores it."""
        import numpy as np

        if isinstance(cache, dict):
            return {k: self._row_slab(v, r) for k, v in cache.items()}
        return np.asarray(jax.device_get(cache[:, r : r + 1]))

    def _swap_account(self, d_bytes: int, d_rows: int) -> None:
        from ..obs.metrics import swap_host_adjust

        self._swap_bytes = max(0, self._swap_bytes + d_bytes)
        self._swap_rows = max(0, self._swap_rows + d_rows)
        swap_host_adjust(d_bytes, rows=d_rows)

    def preempt(
        self, request: GenerationRequest, policy: str = "swap"
    ) -> "Optional[PreemptedRow]":
        """Retire a live row NOW — like :meth:`cancel` — but capture
        everything :meth:`resume_begin` needs to continue it later with
        an unchanged token stream: the exact host copy of the row's
        control leaves (last token, rng key, presence, offsets,
        remaining budget) plus, under ``policy="swap"``, its KV payload
        (own pool pages spilled via ``PagePool.swap_out``; the
        contiguous row slab / stacked side-cache row copied to host).
        Shared CoW prefix pages are refcounted by other readers and are
        RELEASED, never swapped — resume re-shares them from the prefix
        store. ``policy="recompute"`` captures no payload (the KV is
        re-prefilled from prompt + generated tokens at resume).

        Speculating rows round-trip too (ISSUE 16): a model/cross row's
        draft-cache row and draft offset are captured under ``swap``
        (and re-prefilled via the resume's draft chunks under
        ``recompute``); an ngram row's history is rebuilt host-side
        from prompt + generated at resume. The rng key capture is the
        same one the plain path does — in spec mode the key advances
        once per ROUND, so the resumed row's remaining sampled stream
        is bit-exact either way.

        Returns None — and leaves the row running — when the row cannot
        be preempted safely: no live row for ``request``, or a
        recompute whose re-prefill could not fit this session's static
        shapes."""
        from .jax_engine import _prompt_alloc

        self._refuse_bundles("preemption")
        if self.closed:
            return None
        slot = None
        for r, row in enumerate(self.rows):
            if row is not None and row.request is request:
                slot = r
                break
        if slot is None:
            return None
        r, row = slot, self.rows[slot]
        if policy == "recompute":
            # stacked sessions keep generated KV in the side caches; a
            # re-prefill would have to fold it into pool pages under a
            # shifted prompt boundary — swap is the supported policy
            if self.paged and self.stacked:
                return None
            total = self.s_prefilled(row)
            if not self.paged and _prompt_alloc(total) > self.cache_len:
                return None  # re-prefill would not fit the session cache
            if (
                self.spec is not None
                and self.spec["draft"] is not None
                and _prompt_alloc(total) > self.spec_draft_len
            ):
                return None  # draft re-prefill would not fit its cache
        ids = self.tok.encode(request.prompt)
        pr = PreemptedRow(request, ids, row.generated, row.s_real)
        pr.policy = policy
        pr.paged = self.paged
        pr.stacked = bool(self.paged and self.stacked)
        pr.offsets = int(jax.device_get(self.offsets[r]))
        pr.remaining = int(jax.device_get(self.remaining[r]))
        pr.rng = jax.device_get(self.rngs[r])
        pr.use_top_p = request.top_p < 1.0
        pr.use_rp = request.repeat_penalty != 1.0
        if pr.use_rp:
            pr.presence = jax.device_get(self.presence[r])
        pr.streamed = row.streamed
        pr.t0, pr.t1 = row.t0, row.t1
        # the attribution account parks with the victim (ISSUE 20):
        # restored by _commit_resume, so a preempted-and-resumed row's
        # close-out still covers every slice it ever rode
        pr.attr_wall = row.attr_wall
        pr.attr_J = row.attr_J
        pr.attr_J_low = row.attr_J_low
        pr.attr_J_high = row.attr_J_high
        pr.attr_slices = row.attr_slices
        pr.attr_wasted_J = row.attr_wasted_J
        host_bytes = 0
        if (
            self.spec is not None
            and self.spec["draft"] is not None
            and policy == "swap"
        ):
            # the draft cache's row travels with the victim (it is tiny
            # — a few prompt+budget positions of a small model); ngram
            # rows need nothing captured, their history rebuilds from
            # prompt + generated
            pr.draft_blob = (
                self._row_slab(self.carry["draft_k"], r),
                self._row_slab(self.carry["draft_v"], r),
            )
            pr.draft_offset = int(
                jax.device_get(self.carry["draft_offsets"][r])
            )
            host_bytes += _slab_bytes(pr.draft_blob[0]) + _slab_bytes(
                pr.draft_blob[1]
            )
        if self.paged:
            pages = list(row.pages)
            shared_n = 0
            while (
                shared_n < len(pages)
                and self.pool.refcount(pages[shared_n]) > 1
            ):
                shared_n += 1
            if any(self.pool.refcount(p) > 1 for p in pages[shared_n:]):
                # shared pages past the leading prefix run would break
                # the table-rebuild invariant — refuse, keep it running
                return None
            pr.shared_pages = pages[:shared_n]
            own = pages[shared_n:]
            pr.n_own_pages = len(own)
            # ordering discipline (same as _retire/cancel): park the
            # table row BEFORE any page returns to the free list
            self._park_row(r)
            if policy == "swap":
                if self.stacked:
                    side = (
                        self._row_slab(self.side_k, r),
                        self._row_slab(self.side_v, r),
                    )
                    pr.side_blob = side
                    side_bytes = _slab_bytes(side[0]) + _slab_bytes(side[1])
                    from ..obs.metrics import observe_swap

                    observe_swap("out", side_bytes)
                    host_bytes += side_bytes
                if own:
                    pr.blob = self.pool.swap_out(own)
                    host_bytes += pr.blob.nbytes
            else:
                if own:
                    self.pool.free(own)
            if pr.shared_pages:
                self.pool.free(pr.shared_pages)  # drop OUR reference only
            row.pages = []
        elif policy == "swap":
            from ..obs.metrics import observe_swap

            pr.cache_blob = (
                self._row_slab(self.k_cache, r),
                self._row_slab(self.v_cache, r),
            )
            cache_bytes = _slab_bytes(pr.cache_blob[0]) + _slab_bytes(
                pr.cache_blob[1]
            )
            host_bytes += cache_bytes
            observe_swap("out", cache_bytes)
        pr.host_bytes = host_bytes
        self._swap_account(host_bytes, 1 if host_bytes else 0)
        # device-side retirement, exactly as cancel(): the slot rides
        # along pre-done from the next slice
        self.done = self.done.at[r].set(True)
        self.remaining = self.remaining.at[r].set(0)
        self.rows[r] = None
        self._recommit_carry()
        return pr

    @staticmethod
    def s_prefilled(row_or_pr) -> int:
        """Positions of KV a row has materialised: prompt + generated
        minus the last token (sampled but not yet fed through the
        model). This is what a recompute resume re-prefills."""
        if isinstance(row_or_pr, PreemptedRow):
            return len(row_or_pr.ids) + len(row_or_pr.generated) - 1
        return row_or_pr.s_real + len(row_or_pr.generated) - 1

    def _resume_plan(self, pr: "PreemptedRow") -> "Optional[Dict[str, Any]]":
        """How ``pr`` can re-enter this session RIGHT NOW: ``{"mode":
        "swap"|"recompute", "need": free-list pages required, "entry":
        prefix entry to re-share from}`` — or None when it cannot (a
        stacked victim whose swap blob degraded, a recompute that no
        longer fits). Side-effect free; ``can_resume`` probes it."""
        if pr.request.model != self.model:
            return None
        if self.spec is not None:
            # the resumed row inherits this session's spec config: its
            # prefilled history + remaining budget must fit the fixed
            # draft cache / ngram history alongside the rounds-
            # overshoot margin
            need_len = self.s_prefilled(pr) + pr.remaining + 1
            if need_len + self.spec_margin > self.spec_draft_len:
                return None
        if not self.paged:
            if pr.policy == "swap" and pr.cache_blob is not None:
                return {"mode": "swap", "need": 0, "reshare": False}
            from .jax_engine import _prompt_alloc

            if _prompt_alloc(self.s_prefilled(pr)) > self.cache_len:
                return None
            return {"mode": "recompute", "need": 0, "reshare": False}
        total_need = self._pages_needed(
            len(pr.ids), pr.request.max_new_tokens
        )
        if pr.policy == "swap":
            if not pr.shared_pages:
                return {"mode": "swap", "need": pr.n_own_pages, "reshare": False}
            if self.store is not None:
                # the victim's released shared pages must STILL be the
                # store's leading device-resident run for this prompt —
                # ids drifted (spill, eviction, a different restore)
                # means the captured mapping is stale
                run = self.store.hbm_run(self.model, pr.ids)
                held = run[: len(pr.shared_pages)]
                if held == list(pr.shared_pages) and all(
                    self.pool.refcount(p) >= 1 for p in held
                ):
                    return {
                        "mode": "swap",
                        "need": pr.n_own_pages,
                        "reshare": True,
                    }
            # the shared prefix left the store (or spilled) while the
            # victim was parked: its pages may have been recycled —
            # degrade to a full recompute (stacked sessions cannot,
            # see preempt)
            if self.stacked:
                return None
            return {"mode": "recompute", "need": total_need, "reshare": False}
        if self.stacked:
            return None
        return {"mode": "recompute", "need": total_need, "reshare": False}

    def can_resume(self, pr: "PreemptedRow") -> bool:
        """Whether the preempted row fits back RIGHT NOW (free slot +
        pages for its plan). Side-effect free — the scheduler probes
        between slices, exactly like ``can_join``."""
        if self.closed or self.free_slots == 0:
            return False
        plan = self._resume_plan(pr)
        if plan is None:
            return False
        return not self.paged or plan["need"] <= self.pool.free_pages

    def resume_begin(
        self,
        pr: "PreemptedRow",
        chunk_tokens: Optional[int] = None,
    ) -> _PendingJoin:
        """Start re-admitting a preempted row through the chunked-join
        machinery: reserve a free slot and its pages (swap: the blob's
        page count, shared prefix pages re-shared from the store;
        recompute: the row's full footprint), and — recompute only —
        split the re-prefill of prompt + generated-so-far into
        token-budgeted chunks that interleave with decode slices like
        any joiner's. Commit (``join_commit``) restores the KV and
        re-seats the row; a swap resume has zero chunks and commits on
        the scheduler's next interleave turn."""
        from .jax_engine import (
            JOIN_PREFILL_CHUNK_TOKENS,
            PROMPT_BUCKETS,
            _floor_bucket,
            _prompt_chunks,
        )

        self._refuse_bundles("migration")

        if self.closed:
            raise RuntimeError("session is closed")
        plan = self._resume_plan(pr)
        if plan is None or self.free_slots == 0:
            raise RuntimeError("preempted row cannot resume in this session")
        r = next(
            i
            for i, row in enumerate(self.rows)
            if row is None and i not in self._pending
        )
        mode = plan["mode"]
        pages: List[int] = []
        if self.paged:
            if mode == "swap":
                own = self.pool.alloc(
                    pr.n_own_pages, shard=self._row_shard(r)
                )
                if pr.shared_pages:
                    self.pool.share(pr.shared_pages)
                    if plan.get("reshare") and self.store is not None:
                        self.store.touch(self.model, pr.ids)
                pages = list(pr.shared_pages) + own
            else:
                pages = self.pool.alloc(
                    plan["need"], shard=self._row_shard(r)
                )
        if mode == "swap":
            ids, chunks, cache_len = pr.ids, [], 0
            k_cache = v_cache = None
        else:
            ids = pr.ids + pr.generated[:-1]
            chunk = _floor_bucket(
                int(chunk_tokens or JOIN_PREFILL_CHUNK_TOKENS),
                PROMPT_BUCKETS,
            )
            chunks = _prompt_chunks(len(ids), chunk)
            if self.paged:
                cache_len = chunks[-1][0] + chunks[-1][1]
            else:
                cache_len = self.cache_len
                if chunks[-1][0] + chunks[-1][1] > cache_len:
                    chunks = _prompt_chunks(len(ids), None)
                if chunks[-1][0] + chunks[-1][1] > cache_len:
                    if pages:
                        self.pool.free(pages)
                    raise RuntimeError(
                        "resume re-prefill does not fit the session cache"
                    )
            k_cache, v_cache = self._private_cache(cache_len)
        if pr.presence is not None:
            presence = jnp.asarray(pr.presence)[None]
        else:
            presence = jnp.zeros((1, self.cfg.vocab_size), dtype=bool)
        pending = _PendingJoin(
            pr.request, r, ids, chunks, cache_len,
            k_cache, v_cache, presence, pages,
        )
        pending.resume = pr
        pending.resume_mode = mode
        if (
            self.spec is not None
            and self.spec["draft"] is not None
            and not (mode == "swap" and pr.draft_blob is not None)
        ):
            # the resumed row needs a draft cache but no blob survived
            # (recompute policy, or a victim captured by a non-
            # speculating session): re-prefill the draft over the FULL
            # history — prompt + generated-so-far — in chunks that
            # interleave exactly like a joiner's
            eng = self.engine
            tf_d = eng._models[self.spec["draft"]]
            dk, dv = tf_d.init_cache(1, self.spec_draft_len, dtype=eng.dtype)
            pending.draft_k, pending.draft_v = eng._place_cache(
                dk, dv, self.spec["dcfg"]
            )
            pending.draft_ids = pr.ids + pr.generated[:-1]
            chunk_w = _floor_bucket(
                int(chunk_tokens or JOIN_PREFILL_CHUNK_TOKENS),
                PROMPT_BUCKETS,
            )
            pending.draft_chunks = _prompt_chunks(
                len(pending.draft_ids), chunk_w
            )
        self._pending[r] = pending
        return pending

    def resume_discard(self, pr: "PreemptedRow") -> None:
        """Drop a parked victim for good (its ticket was cancelled, its
        deadline passed, or the session is shutting down): settle the
        swap ledger so the host-residency gauges return exactly to
        their idle values. Idempotent; a closed session already settled
        its whole ledger."""
        if pr.discharged:
            return
        pr.discharged = True
        if pr.host_bytes and not self.closed:
            self._swap_account(-pr.host_bytes, -1)
        pr.host_bytes = 0

    def _commit_resume(self, pending: _PendingJoin) -> int:
        """Finish a resume: restore the KV payload (swap: scatter the
        host blob into the reserved pages / set the row slabs back;
        recompute: the freshly re-prefilled private cache installs like
        any join's, in ``_seat_row``'s one program) and re-seat the row
        with its captured control state — same last token, rng key,
        presence and remaining budget, so the continued stream is
        bit-identical to the uninterrupted run."""
        from ..obs.metrics import observe_swap

        pr = pending.resume
        r = pending.slot
        del self._pending[r]
        mode = pending.resume_mode
        if mode == "swap":
            if self.paged:
                own = pending.pages[len(pr.shared_pages) :]
                if pr.blob is not None:
                    # pool.k/v alias the carry leaves; swap_in replaces
                    # them, so re-sync the carry to the new arrays
                    self.pool.swap_in(pr.blob, pages=own)
                    self.carry["pool_k"] = self.pool.k
                    self.carry["pool_v"] = self.pool.v
                if self.stacked and pr.side_blob is not None:
                    sk, sv = pr.side_blob
                    self.side_k = _set_row(
                        self.side_k, r, jax.tree.map(jnp.asarray, sk)
                    )
                    self.side_v = _set_row(
                        self.side_v, r, jax.tree.map(jnp.asarray, sv)
                    )
                    observe_swap(
                        "in", _slab_bytes(sk) + _slab_bytes(sv)
                    )
            else:
                kb, vb = pr.cache_blob
                self.k_cache = _set_row(
                    self.k_cache, r, jax.tree.map(jnp.asarray, kb)
                )
                self.v_cache = _set_row(
                    self.v_cache, r, jax.tree.map(jnp.asarray, vb)
                )
                observe_swap("in", _slab_bytes(kb) + _slab_bytes(vb))
        if self.spec is not None:
            # re-install the row's draft-source state (ISSUE 16): the
            # captured draft-cache row (swap) or the freshly
            # re-prefilled one (recompute); ngram rebuilds its history
            # from the token stream the host already holds. Round
            # counters restart at zero — this slot's prior occupant
            # stats must not leak into the resumed row's attribution.
            if self.spec["draft"] is not None:
                if pending.draft_k is not None:
                    dk_row, dv_row = pending.draft_k, pending.draft_v
                    doff = len(pending.draft_ids or pending.ids)
                else:
                    dkb, dvb = pr.draft_blob
                    dk_row = jax.tree.map(jnp.asarray, dkb)
                    dv_row = jax.tree.map(jnp.asarray, dvb)
                    doff = pr.draft_offset
                self.carry["draft_k"] = _set_row(
                    self.carry["draft_k"], r, dk_row
                )
                self.carry["draft_v"] = _set_row(
                    self.carry["draft_v"], r, dv_row
                )
                self.carry["draft_offsets"] = (
                    self.carry["draft_offsets"].at[r].set(doff)
                )
            else:
                self._set_ngram_row(r, pr.ids + pr.generated)
            for ckey in (
                "spec_rounds", "spec_accepted", "spec_drafted",
                "spec_rejected",
            ):
                self.carry[ckey] = self.carry[ckey].at[r].set(0)
            self._spec_draft_wasted[r] = 0.0
        # settle the ledger: the victim's KV left host memory (swap) or
        # its blob is obsolete (recompute degraded from swap)
        if pr.host_bytes:
            self._swap_account(-pr.host_bytes, -1)
            pr.host_bytes = 0
        pr.discharged = True
        self._seat_row(
            pr.request,
            r,
            first_token=pr.generated[-1],
            rng=jnp.asarray(pr.rng),
            presence=pending.presence,
            offsets=pr.offsets,
            prompt_len=pr.prompt_len,
            remaining=pr.remaining,
            use_top_p=pr.use_top_p,
            use_rp=pr.use_rp,
            pages=pending.pages,
            t0=pr.t0,
            t1=pr.t1,
            t_decode0=time.monotonic(),
            generated=pr.generated,
            streamed=pr.streamed,
            shared=len(pr.shared_pages) if mode == "swap" else 0,
            # recompute: the private cache now holds KV for every
            # prefilled position and installs exactly like a join's
            # (prefilled length plays the "prompt" role; shared base 0)
            cache=(
                None
                if mode == "swap"
                else (pending.k_cache, pending.v_cache, len(pending.ids), 0)
            ),
        )
        # restore the parked attribution account + whatever the resume's
        # own re-prefill chunks billed while pending (recompute mode)
        row = self.rows[r]
        row.attr_wall = pr.attr_wall + pending.attr_wall
        row.attr_J = pr.attr_J + pending.attr_J
        row.attr_J_low = pr.attr_J_low + pending.attr_J_low
        row.attr_J_high = pr.attr_J_high + pending.attr_J_high
        row.attr_slices = pr.attr_slices
        row.attr_wasted_J = pr.attr_wasted_J
        return r

    def _recommit_carry(self) -> None:
        """Re-pin the carry to the engine's declared placements after a
        host-side eager mutation batch (a cancel, a swap-in, a
        speculative row's draft state). Eager ops let GSPMD choose
        output shardings, and on a mesh a leaf can drift — e.g. a
        REPLICATED-KV pool (heads don't divide ``tp``) picks up a
        partial GSPMD sharding from a swap-in's page scatter — which the
        next program's explicit ``in_shardings`` would reject. The row
        install declares the placements as its output shardings and
        needs none of this afterwards.
        ``device_put`` to the declared sharding is identity for leaves
        already in place, a reshard for any that drifted; a no-op
        entirely on single-device engines (_place_carry is identity)."""
        self.carry = self.engine._place_carry(
            self.cfg, self.carry, draft_cfg=self._draft_cfg()
        )
        if self.paged:
            self.pool.k = self.carry["pool_k"]
            self.pool.v = self.carry["pool_v"]

    # -- admission ------------------------------------------------------------
    def can_join(self, request: GenerationRequest) -> bool:
        """Whether ``request`` fits this session's static shapes and free
        capacity RIGHT NOW. Must stay side-effect free — the scheduler
        probes before paying the prefill."""
        from .jax_engine import GEN_BUCKETS, _bucket, _prompt_alloc

        if self.closed or self.free_slots == 0:
            return False
        if request.model != self.model or request.top_k != self.top_k:
            return False
        ids = self.tok.encode(request.prompt)
        ids_len = len(ids)
        if ids_len == 0:
            return False  # would fail prefill; let the solo path 400 it
        if ids_len + request.max_new_tokens > self.cfg.max_seq_len:
            return False
        if self.spec is not None:
            # A speculating session admits any ELIGIBLE joiner — greedy
            # rows verify by argmax match, sampled rows (ISSUE 16) by
            # rejection resampling, selected per row inside the one
            # compiled step; only repeat-penalty rows and
            # hotter-than-spec_temperature_max rows defer to their own
            # session. The joiner inherits the session's spec config,
            # so its prompt + budget must fit the fixed draft cache (or
            # ngram history buffer) alongside the rounds-overshoot
            # margin.
            if not self.engine._spec_eligible(request):
                return False
            if (
                _prompt_alloc(ids_len)
                + _bucket(request.max_new_tokens, GEN_BUCKETS)
                + self.spec_margin
                > self.spec_draft_len
            ):
                return False
        if not self.paged:
            return (
                _prompt_alloc(ids_len)
                + _bucket(request.max_new_tokens, GEN_BUCKETS)
                <= self.cache_len - self.spec_margin
            )
        if self.stacked and request.max_new_tokens - 1 > self.g_bucket:
            return False  # the side caches hold g_bucket columns
        need = self._pages_needed(ids_len, request.max_new_tokens)
        if need > self.jmax:
            return False
        # Shared-prefix billing (unchanged from ISSUE 7): pages mapped
        # from the store are billed ONCE — only the divergent tail's
        # pages come off the free list. Spilled prefix nodes add their
        # RESTORE pages to the free-list requirement (store pages, not
        # row pages); when a restore would not fit, the plan degrades
        # to the already-resident leading run, then to seed-only.
        hit = self._prefix_hit(ids)
        free = self.pool.free_pages
        if hit is None:
            return need <= free
        own_full = need - hit["full_pages"]
        if own_full + hit["restore_pages"] <= free:
            return True
        # degraded plan: map only the already-resident leading run
        return need - len(hit["hbm_lead"]) <= free

    def join(self, request: GenerationRequest) -> int:
        """Admit ``request`` into a free slot, paying the WHOLE prompt
        prefill now (decode from the next slice) — the synchronous
        one-shot join, kept for callers that don't interleave (and as
        the `--no-chunked-joins`-style baseline the chunked_join bench
        A/Bs against). Implemented over the resumable protocol below so
        the two paths cannot drift. Returns the slot index. Callers
        should probe :meth:`can_join` first; a failed prefill raises and
        leaves the session consistent (the slot stays free)."""
        from .jax_engine import PREFILL_CHUNK

        pending = self.join_begin(request, chunk_tokens=PREFILL_CHUNK)
        try:
            while not self.join_step(pending):
                pass
            return self.join_commit(pending)
        except BaseException:
            self.join_abort(pending)
            raise

    def join_begin(
        self,
        request: GenerationRequest,
        chunk_tokens: Optional[int] = None,
    ) -> _PendingJoin:
        with TRACER.span("session.join.begin"):
            return self._join_begin(request, chunk_tokens)

    def _join_begin(
        self,
        request: GenerationRequest,
        chunk_tokens: Optional[int] = None,
    ) -> _PendingJoin:
        """Start a RESUMABLE join: reserve a free slot (and, paged, the
        row's pages — so concurrent admissions can't oversubscribe the
        pool while this prefill streams in), build the private solo
        cache, and split the prompt into token-budgeted chunks
        (``chunk_tokens``, default JOIN_PREFILL_CHUNK_TOKENS; floored to
        a compiled prompt-bucket width). No device compute happens here
        — the first :meth:`join_step` runs the first chunk. The budget-
        aware admission cap is the caller's to re-evaluate before this
        call (serve/scheduler.py does, per joiner)."""
        from .jax_engine import (
            JOIN_PREFILL_CHUNK_TOKENS,
            PROMPT_BUCKETS,
            _floor_bucket,
            _prompt_chunks,
        )

        if not self.can_join(request):
            raise RuntimeError("request cannot join this session")
        r = next(
            i
            for i, row in enumerate(self.rows)
            if row is None and i not in self._pending
        )
        eng = self.engine
        ids = self.tok.encode(request.prompt)
        chunk = _floor_bucket(
            int(chunk_tokens or JOIN_PREFILL_CHUNK_TOKENS), PROMPT_BUCKETS
        )
        # Shared-prefix hit (engine/radix_store.py): the leading
        # `common` positions are SEEDED from the store's slab instead
        # of recomputed — the chunk list covers only the divergent
        # tail, at absolute offsets (join_step's prefill already takes
        # any start offset against the partially-filled private cache).
        hit = self._prefix_hit(ids)
        seed = None
        if hit is not None:
            # fetch the host seed BEFORE committing to the plan: a hit
            # whose path raced an eviction degrades to a plain join
            seed = self.store.seed(self.model, ids, hit["common"])
            if seed is None:
                hit = None
        common = hit["common"] if hit is not None else 0

        def _tail_chunks(common_, chunk_):
            return [
                (common_ + s, b)
                for s, b in _prompt_chunks(len(ids) - common_, chunk_)
            ]

        chunks = _tail_chunks(common, chunk)
        alloc = chunks[-1][0] + chunks[-1][1]
        if self.paged:
            # private cache covers just the prompt; commit scatters whole
            # pages (the generation region lives in the pool/side caches)
            cache_len = alloc
        else:
            cache_len = self.cache_len
            if alloc > cache_len:
                # the budgeted chunking's bucket rounding overshot the
                # session cache; fall back to the standard chunk width,
                # then use LESS of the hit until the tail's bucketed end
                # fits (can_join's _prompt_alloc check guarantees the
                # common=0 chunking fits)
                chunks = _tail_chunks(common, None)
                while common > 0 and chunks[-1][0] + chunks[-1][1] > cache_len:
                    common -= 1
                    chunks = _tail_chunks(common, None)
                if common == 0:
                    hit = None
        pages: List[int] = []
        shared = 0
        if self.paged:
            need = self._pages_needed(len(ids), request.max_new_tokens)
            shared_ids: List[int] = []
            if hit is not None and common // self.page_size:
                # SPILLED prefix nodes on the matched path swap back in
                # first (fresh store pages — llm_prefix_store_restores);
                # a restore that no longer fits degrades the plan to the
                # already-resident leading run. pool.k/v are replaced by
                # a swap-in scatter, so the carry re-syncs + re-pins.
                own_full = need - hit["full_pages"]
                if (
                    hit["restore_nodes"]
                    and own_full + hit["restore_pages"]
                    <= self.pool.free_pages
                ):
                    self.store.restore(self.model, ids, common)
                    self.carry["pool_k"] = self.pool.k
                    self.carry["pool_v"] = self.pool.v
                    self._recommit_carry()
                plan = self.store.page_plan(self.model, ids, common)
                shared_ids = plan["hbm_lead"]
            shared = len(shared_ids)
            pages = self.pool.alloc(need - shared, shard=self._row_shard(r))
            if shared:
                # map the read-only prefix pages into this row: one
                # reference per sharer — recycled only when the LAST
                # reader (rows, store nodes) frees them
                self.pool.share(shared_ids)
                pages = list(shared_ids) + pages
        k_cache, v_cache = self._private_cache(cache_len)
        if common and hit is not None:
            # seed the private prefill cache with the store's exact
            # pre-quantization K/V: the tail prefill attends to the
            # prefix at solo precision (token parity, incl. int8 pools).
            # The contiguous overflow loop above may have REDUCED
            # common — the slab slices down to it.
            k_seed, v_seed = seed
            k_cache = jax.lax.dynamic_update_slice(
                k_cache,
                jnp.asarray(k_seed[:, :, :common])[:, None].astype(
                    k_cache.dtype
                ),
                (0, 0, 0, 0, 0),
            )
            v_cache = jax.lax.dynamic_update_slice(
                v_cache,
                jnp.asarray(v_seed[:, :, :common])[:, None].astype(
                    v_cache.dtype
                ),
                (0, 0, 0, 0, 0),
            )
            self.store.record_hit(self.model, ids)
            from .prefix import observe_hit

            # CoW: seeded positions past the last SHARED page boundary
            # are copied into the joiner's own first partial page at
            # commit (paged) / live only in its private cache (contig)
            observe_hit(
                common,
                shared,
                cow=self.paged and common > shared * self.page_size,
            )
        else:
            common = 0
        presence = jnp.zeros((1, self.cfg.vocab_size), dtype=bool)
        if request.repeat_penalty != 1.0:
            presence = presence.at[0, jnp.asarray(ids)].set(True)
        pending = _PendingJoin(
            request, r, ids, chunks, cache_len, k_cache, v_cache,
            presence, pages,
            hit_tokens=common, shared_pages=shared,
        )
        if self.spec is not None and self.spec["draft"] is not None:
            # the joiner inherits the session's spec config: a private
            # draft cache prefills over the FULL prompt (a prefix hit
            # seeds the TARGET only — the draft is cheap to recompute)
            # in chunks that interleave exactly like the target's. The
            # ngram source needs neither cache nor chunks — its history
            # row installs host-side at commit.
            tf_d = eng._models[self.spec["draft"]]
            dk, dv = tf_d.init_cache(1, self.spec_draft_len, dtype=eng.dtype)
            pending.draft_k, pending.draft_v = eng._place_cache(
                dk, dv, self.spec["dcfg"]
            )
            pending.draft_chunks = _prompt_chunks(len(ids), chunk)
        self._pending[r] = pending
        return pending

    def join_step(self, pending: _PendingJoin) -> bool:
        with TRACER.span("session.join.prefill", slot=pending.slot):
            return self._join_step(pending)

    def _join_step(self, pending: _PendingJoin) -> bool:
        """Run ONE prefill chunk of a pending join (offset>0 against the
        private cache — the engine's chunked-prefill path). Returns True
        once the whole prompt is prefilled (commit next). Fenced, so the
        caller's wall-clock around this call IS the in-flight rows'
        stall for this chunk. In a speculative session the joiner's
        DRAFT prefill rides the same machinery: target chunks run
        first (they gate the first token), then the draft's — still one
        chunk forward per call, so the interleave's stall bound holds.
        """
        eng = self.engine
        if pending.next_chunk < len(pending.chunks):
            tf = eng._models[self.model]
            t0 = time.monotonic()
            start, bucket = pending.chunks[pending.next_chunk]
            ids = pending.ids[start : start + bucket]
            real = len(ids)
            tokens = jnp.asarray(
                [ids + [self.tok.pad_id] * (bucket - real)], dtype=jnp.int32
            )
            with eng._stepped_compute_ctx():
                prefill = eng._prefill_fn(
                    self.model, bucket, pending.cache_len
                )
                logits, pending.k_cache, pending.v_cache = prefill(
                    tf.params,
                    tokens,
                    jnp.int32(start),
                    jnp.asarray([real - 1]),
                    pending.k_cache,
                    pending.v_cache,
                )
                jax.block_until_ready(logits)
            pending.logits = logits
            pending.next_chunk += 1
            dt = time.monotonic() - t0
            pending.prefill_s += dt
            if _obs_enabled():
                # the chunk's wall/Joules bill to the JOINER (ISSUE 20):
                # the in-flight rows only stalled for it
                try:
                    self._attr_chunk(pending, start, real, dt)
                except Exception:  # noqa: BLE001 — telemetry only
                    pass
        elif (
            self.spec is not None
            and pending.draft_next < len(pending.draft_chunks)
        ):
            draft = self.spec["draft"]
            tf_d = eng._models[draft]
            t0 = time.monotonic()
            start, bucket = pending.draft_chunks[pending.draft_next]
            draft_ids = pending.draft_ids or pending.ids
            ids = draft_ids[start : start + bucket]
            real = len(ids)
            tokens = jnp.asarray(
                [ids + [self.tok.pad_id] * (bucket - real)], dtype=jnp.int32
            )
            with eng._stepped_compute_ctx():
                prefill = eng._prefill_fn(
                    draft, bucket, self.spec_draft_len
                )
                dlogits, pending.draft_k, pending.draft_v = prefill(
                    tf_d.params,
                    tokens,
                    jnp.int32(start),
                    jnp.asarray([real - 1]),
                    pending.draft_k,
                    pending.draft_v,
                )
                jax.block_until_ready(dlogits)
            pending.draft_next += 1
            dt = time.monotonic() - t0
            pending.prefill_s += dt
            if _obs_enabled():
                # draft chunks bill wall only: the draft model's Joules
                # are priced per round by the spec waste machinery, and
                # this session's cfg would misprice the small model
                try:
                    tot = self._attr_totals
                    tot["wall"] += dt
                    pending.attr_wall += dt
                except Exception:  # noqa: BLE001 — telemetry only
                    pass
        # a session that fell back to plain decode mid-join simply stops
        # needing the draft chunks (the row decodes plainly from commit)
        draft_done = (
            self.spec is None
            or pending.draft_next >= len(pending.draft_chunks)
        )
        return pending.next_chunk >= len(pending.chunks) and draft_done

    def join_commit(self, pending: _PendingJoin) -> int:
        with TRACER.span("session.join.commit", slot=pending.slot):
            return self._join_commit(pending)

    def _join_commit(self, pending: _PendingJoin) -> int:
        """Finish a fully-prefilled pending join: sample the first token
        (exactly as the solo path's ``_start`` — same rng derivation,
        same sampler call — so the joiner's stream stays bit-identical
        to its solo ``generate()``) and install the row into the
        session. Only now does the row enter the decode done-mask
        bookkeeping. Returns the slot index."""
        from ..ops.sampling import sample_token

        if pending.resume is not None:
            # a preemption resume riding the same machinery: no first
            # token is sampled — the captured one continues the stream
            if pending.next_chunk < len(pending.chunks) or (
                self.spec is not None
                and pending.draft_next < len(pending.draft_chunks)
            ):
                raise RuntimeError(
                    f"resume not fully re-prefilled: chunk "
                    f"{pending.next_chunk} of {len(pending.chunks)} "
                    f"(+draft {pending.draft_next} of "
                    f"{len(pending.draft_chunks)})"
                )
            return self._commit_resume(pending)
        if pending.next_chunk < len(pending.chunks):
            raise RuntimeError(
                f"join not fully prefilled: chunk {pending.next_chunk} of "
                f"{len(pending.chunks)}"
            )
        request = pending.request
        use_top_p = request.top_p < 1.0
        use_rp = request.repeat_penalty != 1.0
        t0 = time.monotonic()
        rng = jax.random.PRNGKey(request.seed)
        rng, sub = jax.random.split(rng)
        presence = pending.presence
        with self.engine._stepped_compute_ctx():
            first = sample_token(
                pending.logits,
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
        dt = time.monotonic() - t0
        pending.prefill_s += dt
        if _obs_enabled():
            # the first-token sample is the joiner's work too (wall
            # only — sampling is not a weight/KV stream the model prices)
            self._attr_totals["wall"] += dt
            pending.attr_wall += dt
        if _obs_enabled():
            try:
                from .jax_engine import _PREFILL_H

                # the sum of chunk walls, not the interleaved span — the
                # decode slices between chunks are not prefill time
                _PREFILL_H.observe(pending.prefill_s)
            except Exception:  # noqa: BLE001 — telemetry only
                pass
        r = pending.slot
        del self._pending[r]
        if self.spec is not None:
            # install the joiner's draft-source row BEFORE _install_row,
            # whose _recommit_carry re-pins every leaf these eager
            # writes touched before the row program takes the carry
            if self.spec["draft"] is not None:
                self.carry["draft_k"] = _set_row(
                    self.carry["draft_k"], r, pending.draft_k
                )
                self.carry["draft_v"] = _set_row(
                    self.carry["draft_v"], r, pending.draft_v
                )
                self.carry["draft_offsets"] = (
                    self.carry["draft_offsets"].at[r].set(len(pending.ids))
                )
            else:
                # ngram: the joiner's history row is its prompt + the
                # first token just sampled — a host-side int32 write
                self._set_ngram_row(r, pending.ids + [int(first[0])])
            for ckey, hkey in (
                ("spec_rounds", "rounds"),
                ("spec_accepted", "accepted"),
                ("spec_drafted", "drafted"),
                ("spec_rejected", "rejected"),
            ):
                self.carry[ckey] = self.carry[ckey].at[r].set(0)
                self._spec_host[hkey][r] = 0
            self._spec_draft_wasted[r] = 0.0
        with TRACER.span("session.join.install") as install_span:
            programs0 = self.row_programs
            n_pages = self._install_row(
                request,
                r,
                s_real=len(pending.ids),
                first=first,
                rng=rng,
                presence=presence,
                k_cache=pending.k_cache,
                v_cache=pending.v_cache,
                use_top_p=use_top_p,
                use_rp=use_rp,
                pages=pending.pages,
                t0=pending.t0,
                prefill_s=pending.prefill_s,
                shared_pages=pending.shared_pages,
            )
            if install_span is not None:
                # device programs the install dispatched, pages it wrote
                install_span.attrs.update(
                    programs=self.row_programs - programs0, pages=n_pages
                )
                if self.cfg.state_layers:
                    # the joiner's recurrent state, written with its pages
                    install_span.attrs["state_bytes"] = state_bytes(
                        pending.k_cache["ssm"]
                    )
        # the chunk walls/Joules billed while pending become the seated
        # row's opening account (ISSUE 20)
        row = self.rows[r]
        row.attr_wall = pending.attr_wall
        row.attr_J = pending.attr_J
        row.attr_J_low = pending.attr_J_low
        row.attr_J_high = pending.attr_J_high
        if self.store is not None:
            # publish at join-commit: the next sharer can seed from THIS
            # prompt's slab (the seeded prefix region is in the private
            # cache too, so the slab is complete) AND map this joiner's
            # own divergent-tail pages — publication is page-backed,
            # uncapped (ISSUE 14).
            self._publish_prefix(
                pending.ids, pending.k_cache, pending.v_cache,
                pending.pages,
            )
        return r

    def join_abort(self, pending: _PendingJoin) -> None:
        """Drop a pending join (failed chunk, scheduler shutdown): the
        slot reservation lifts and its pages return to the pool. The
        private cache is garbage-collected with the object."""
        self._pending.pop(pending.slot, None)
        self._attr_drop(pending)
        if self.paged and pending.pages:
            self.pool.free(pending.pages)
            pending.pages = []

    def _install_row(
        self,
        request: GenerationRequest,
        r: int,
        *,
        s_real: int,
        first,
        rng,
        presence,
        k_cache,
        v_cache,
        use_top_p: bool,
        use_rp: bool,
        pages: "List[int]",
        t0: float,
        prefill_s: float,
        shared_pages: int = 0,
    ) -> int:
        """Install a prefilled solo cache into slot ``r`` and set every
        per-row device/host field — the shared tail of the one-shot and
        chunked joins, and ONE device program (``_seat_row`` with the
        cache: ``_row_program``, the carry donated, compiled at open).
        The first ``shared_pages`` page entries are READ-ONLY mappings
        of store-held prefix pages: the install does not write them
        (their content is the publisher's — writing them would be a
        write to shared state) and the private cache's positions past
        that boundary — the copy-on-write partial page plus the computed
        tail — land in the row's OWN pages. Returns the pool pages
        written, for the ``session.join.install`` span."""
        from .jax_engine import _to_host_list

        return self._seat_row(
            request,
            r,
            # one transfer of the sampled token: ``int(first[0])`` is two
            # eager programs and a transfer, 1 ms of a 2-ms install on
            # the chip, all of it with the device idle
            first_token=_to_host_list(first)[0],
            rng=rng,
            presence=presence,
            offsets=s_real,
            prompt_len=s_real,
            remaining=request.max_new_tokens - 1,
            use_top_p=use_top_p,
            use_rp=use_rp,
            pages=pages,
            t0=t0,
            t1=t0 + prefill_s,
            t_decode0=time.monotonic(),
            shared=shared_pages,
            cache=(k_cache, v_cache, s_real, shared_pages),
        )

    def _run_row_program(
        self,
        r: int,
        *,
        first_token: int,
        rng,
        presence,
        offsets: int,
        prompt_len: int,
        remaining: int,
        knobs: "tuple[float, float, float]",
        pages: "List[int]",
        cache=None,
    ) -> int:
        """Build ``_row_program``'s ``row`` on the host and run the
        program on the carry: one dispatch, whose few host-built values
        (one int32 and one float32 array) ride in with it. ``cache`` is
        ``(k_cache, v_cache, s_real, shared_pages)`` for an install, None
        to seat a row whose payload is in place. A paged install writes
        the pages that hold positions ``shared_pages * page`` to
        ``s_real``; every other page of the private cache gets the
        pool's page count for a destination, which is outside the pool.
        Returns the pool pages written; ``row_programs`` counts the
        dispatches."""
        import numpy as np

        row = {"rng": rng, "presence": presence}
        s_real = shared_pages = n_written = 0
        if cache is not None:
            row["k"], row["v"], s_real, shared_pages = cache
            if is_state_cache(row["k"]):
                # the joiner's recurrent state rode its chunks beside the
                # attention layers' cache: installed by the same program
                row["ssm"], row["k"] = row["k"]["ssm"], row["k"]["kv"]
        ints = [r, s_real, first_token, offsets, prompt_len, remaining]
        if self.paged:
            table_row = [self._parking_for(r)] * self.jmax
            table_row[: len(pages)] = pages
            ints += table_row
        if self.paged and cache is not None:
            page = self.page_size
            n_prompt_pages = -(-s_real // page)
            base = min(shared_pages, n_prompt_pages)
            dest = [self.pool.n_pages] * -(-row["k"].shape[3] // page)
            dest[base:n_prompt_pages] = pages[base:n_prompt_pages]
            ints += dest
            n_written = n_prompt_pages - base
        row["ints"] = np.asarray(ints, dtype=np.int32)
        row["knobs"] = np.asarray(
            knobs + (KV_INT8_LEVELS,), dtype=np.float32
        )
        install = self.engine._row_install_fn(
            self.model,
            self.carry,
            draft_model=self.spec["draft"] if self.spec is not None else None,
        )
        self.carry = install(row, self.carry)
        self.row_programs += 1
        if self.paged:
            self.pool.k = self.carry["pool_k"]
            self.pool.v = self.carry["pool_v"]
        return n_written

    def _seat_row(
        self,
        request: GenerationRequest,
        r: int,
        *,
        first_token: int,
        rng,
        presence,
        offsets: int,
        prompt_len: int,
        remaining: int,
        use_top_p: bool,
        use_rp: bool,
        pages: "List[int]",
        t0: float,
        t1: float,
        t_decode0: float,
        generated: "Optional[List[int]]" = None,
        streamed: int = 0,
        shared: int = 0,
        cache=None,
    ) -> int:
        """Set every per-row control leaf + the host row record — the
        shared tail of installing a fresh joiner (``offsets ==
        prompt_len``, full budget, ``cache`` its private prefill cache)
        and re-seating a preempted row (captured offsets/remaining/rng,
        generated tokens carried over; ``cache`` the re-prefilled
        history under the recompute policy, None after a swap-in). The
        device side is ONE run of ``_row_program`` either way: it is the
        only writer of a row's control leaves, of its table row and of a
        joiner's pages, and it leaves every leaf on the placement the
        slice step declares. What the host wrote eagerly before this
        call (a speculative row's draft state, a swapped-in payload) is
        re-pinned first, so the program's declared input placements hold.
        Returns the pool pages written."""
        self._recommit_carry()
        n_written = self._run_row_program(
            r,
            first_token=first_token,
            rng=rng,
            presence=presence,
            offsets=offsets,
            prompt_len=prompt_len,
            remaining=remaining,
            knobs=(
                request.temperature,
                self._row_top_p(request),
                request.repeat_penalty,
            ),
            pages=pages,
            cache=cache,
        )
        # sticky for the session: a sentinel makes the filter an identity
        # for rows that never asked for it, so turning a knob on for a
        # joiner cannot perturb a companion's stream
        self.use_top_p = self.use_top_p or use_top_p
        self.use_rp = self.use_rp or use_rp
        if self._spec_host:
            # a re-used slot must not inherit a previous occupant's
            # draft-verify attribution (post-fallback sessions keep the
            # host mirrors for retiring rows' extras)
            for key in self._spec_host:
                self._spec_host[key][r] = 0
        row = _Row(
            request,
            prompt_len,
            first_token,
            request.max_new_tokens - 1,
            t0,
            t1,
            t_decode0,
            pages=pages,
            shared=shared,
        )
        if generated is not None:
            row.generated = list(generated)
        row.side0 = offsets - prompt_len - (len(row.generated) - 1)
        row.streamed = streamed
        self.rows[r] = row
        return n_written

    # -- teardown -------------------------------------------------------------
    def close(self) -> None:
        """Release the session (frees any still-allocated pages). Live
        rows are abandoned — the scheduler fails their tickets; their
        partial token streams are not returned."""
        if self.closed:
            return
        self.closed = True
        if self.spec is not None:
            # the session made it to close without falling back: this
            # source earned its keep — clear any lingering low-acceptance
            # strikes so the next admission doesn't inherit stale blame
            clear = getattr(self.engine, "_spec_source_clear", None)
            if clear is not None:
                clear(self.spec["source"], self.spec["draft"])
        for row in self.rows:
            if row is not None:
                self._attr_drop(row)  # abandoned rows never close out
        for pending in self._pending.values():
            self._attr_drop(pending)
        if self.paged:
            for row in self.rows:
                if row is not None and row.pages:
                    self.pool.free(row.pages)
                    row.pages = []
            for pending in self._pending.values():
                if pending.pages:
                    self.pool.free(pending.pages)
                    pending.pages = []
        if self.store is not None:
            # detach LAST, with every row/pending reference already
            # freed: the store is now each adopted page's SOLE holder,
            # so its device-resident nodes SPILL to host blobs (the
            # swap frees their pages — the pool free-count is exactly
            # restored) and survive this session for the next one
            self.store.detach_pool(self.model, self.pool if self.paged else None)
        self._pending.clear()
        self._stream_tail.clear()
        self.rows = [None] * len(self.rows)
        if self._swap_bytes or self._swap_rows:
            # victims still parked when the session dies: settle the
            # ledger so the host-residency gauges return to idle (the
            # scheduler discards the PreemptedRow objects themselves)
            from ..obs.metrics import swap_host_adjust

            swap_host_adjust(-self._swap_bytes, rows=-self._swap_rows)
            self._swap_bytes = 0
            self._swap_rows = 0
        # release the eviction-guard pins LAST: the weight LRU may now
        # evict this session's models (a deferred eviction retries on
        # the next load's capacity pass)
        closed_hook = getattr(self.engine, "_session_closed", None)
        if closed_hook is not None:
            for name in self._session_pins:
                closed_hook(name)
        self._session_pins = []
