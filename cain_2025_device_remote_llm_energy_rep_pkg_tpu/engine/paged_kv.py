"""Paged KV-cache pool: block-table indirection for mixed-length serving.

The contiguous engine allocates each request's cache at bucket-rounded
shapes; a continuous-batching server with mixed-length concurrent
requests would either pad everyone to the widest shape or re-allocate on
admission. The paged pool fixes the economics the way vLLM does, rebuilt
TPU-first:

- one shared pool of fixed-size pages per layer:
  ``k/v: [L, P, Hkv, page, D]``;
- a request owns ``ceil(len/page)`` page indices (host-side free-list
  allocator — allocation is a scheduler decision, not a device op);
- decode attends through the page table with
  ``ops.pallas_paged_attention.pallas_paged_decode_attention`` — the
  DMA engine is handed per-page base offsets, no gather materialises;
- appends write one token's K/V at ``(page_table[len // page],
  len % page)`` with ``dynamic_update_slice`` — static shapes, jit-safe.

Page size defaults to 128: the lane width the decode kernel tiles on,
and small enough that the worst-case padding per request is < 1 MiB on
8B-class models.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.quantize import KV_INT8_LEVELS, quantize_kv_vector
from ..obs.flight import EV_POOL_EXHAUSTED, FLIGHT
from ..obs.metrics import REGISTRY, enabled as _obs_enabled, observe_swap
from .prefix import PREFIX_SHARED_PAGES_G

DEFAULT_PAGE_SIZE = 128

# Pool-state gauges (obs): a pool lives as long as its session, so the
# gauges track the MOST RECENT pool's state — which is the live one
# while a session decodes, exactly when a scrape wants it.
_POOL_PAGES = REGISTRY.gauge(
    "llm_paged_pool_pages", "Total pages in the most recent page pool"
)
_POOL_FREE = REGISTRY.gauge(
    "llm_paged_pool_free_pages", "Free pages in the most recent page pool"
)
_POOL_OCCUPANCY = REGISTRY.gauge(
    "llm_paged_pool_occupancy",
    "Allocated fraction of the most recent page pool (0..1)",
)
_POOL_FRAGMENTATION = REGISTRY.gauge(
    "llm_paged_pool_fragmentation",
    "1 - (largest contiguous free run / free pages); 0 when free space "
    "is one run or the pool is full",
)
_POOL_EXHAUSTED = REGISTRY.counter(
    "llm_paged_pool_exhausted_total",
    "Allocations refused because the pool had too few free pages",
)


def _fragmentation(free: List[int]) -> float:
    """1 - (largest contiguous free run / free pages); 0 when free space
    is one run or the pool is full. ONE definition — the gauges and the
    /debug/state snapshot must agree."""
    if not free:
        return 0.0
    ordered = sorted(free)
    longest = run = 1
    for a, b in zip(ordered, ordered[1:]):
        run = run + 1 if b == a + 1 else 1
        longest = max(longest, run)
    return 1.0 - longest / len(free)


def _publish_pool_gauges(
    free: List[int], total: int, shared: int = 0
) -> None:
    if not _obs_enabled():
        return
    _POOL_PAGES.set(total)
    _POOL_FREE.set(len(free))
    _POOL_OCCUPANCY.set(1.0 - len(free) / total if total else 0.0)
    _POOL_FRAGMENTATION.set(_fragmentation(free))
    PREFIX_SHARED_PAGES_G.set(shared)


def _codes(leaf):
    """The array that carries a pool leaf's page/shape layout: the int8
    codes of a quantized ``{"q","s"}`` leaf, the array itself otherwise."""
    return leaf["q"] if isinstance(leaf, dict) else leaf


class PagePoolExhausted(RuntimeError):
    """No free pages left — the scheduler must evict or defer admission."""


@dataclasses.dataclass
class PageSwapBlob:
    """Host-resident payload of swapped-out pages (ISSUE 11 preemption):
    page chunks in :func:`scatter_pages` layout — ``[N, L, Hkv, page,
    D]`` numpy arrays (or ``{"q","s"}`` dicts for int8 pools) — so
    :meth:`PagePool.swap_in` is literally one allocation plus one
    scatter. ``nbytes`` is the host footprint the swap gauges account.
    """

    k_chunks: "object"
    v_chunks: "object"
    n_pages: int
    page_size: int
    quantized: bool
    nbytes: int


@dataclasses.dataclass
class PagePool:
    """Device pool + host-side free-list allocator.

    The arrays are functional (every write returns new arrays); the
    allocator is host state owned by whoever schedules requests.

    Allocation is REFCOUNTED (ISSUE 7 shared-prefix paging): ``alloc``
    hands out pages at one reference, :meth:`share` adds a reader (a
    prefix-index entry, a joiner mapping read-only prefix pages into
    its table row), and :meth:`free` drops one reference — a page
    returns to the free list only when its LAST reader lets go. Every
    pre-existing call site (row retirement, cancellation, join abort,
    session close) therefore keeps its exact-free-count contract
    unchanged whether or not its pages are shared.

    ``quantized=True`` makes each pool leaf an int8 ``{"q": codes
    [L, P, Hkv, page, D], "s": f32 scales [L, P, Hkv, page]}`` dict —
    one symmetric scale per (layer, page, head, position) vector, the
    exact scheme of the contiguous int8 KV cache
    (models/quantize.quantize_kv_cache), so a row's quantized stream is
    bit-identical whichever cache layout holds it. Codes are 1 byte and
    the scale is 4 bytes per D-vector: pages are ~(D+4)/2D the bytes of
    bf16 pages — the density that lets paged+int8 admit the larger
    fleet at a fixed KV budget (docs/PERF.md admission A/B).
    """

    k: "jnp.ndarray | dict"  # [L, P, Hkv, page, D] — or {"q","s"}
    v: "jnp.ndarray | dict"
    page_size: int
    _free: List[int] = dataclasses.field(default_factory=list)
    # page index -> live reference count; absent = on the free list
    _refs: Dict[int, int] = dataclasses.field(default_factory=dict)
    # dp row sharding (ISSUE 19): pages partition into ``dp_shards``
    # contiguous equal ranges, aligned with the dp-sharded pool leaf's
    # page-dim split, so shard-tagged allocations keep a row's pages on
    # the device shard that owns the row. Locality is BEST-EFFORT — a
    # starved shard spills into any free page and GSPMD still gathers
    # correctly — so every refcount/exhaustion contract is unchanged.
    dp_shards: int = 1

    @classmethod
    def create(
        cls,
        n_layers: int,
        n_pages: int,
        n_kv_heads: int,
        d_head: int,
        page_size: int = DEFAULT_PAGE_SIZE,
        dtype=jnp.bfloat16,
        quantized: bool = False,
        dp_shards: int = 1,
        d_head_v: Optional[int] = None,
    ) -> "PagePool":
        """``n_layers`` counts attention blocks (``cfg.cache_layers``),
        ``d_head`` is the K leaf's row width. ``d_head_v`` (default: the
        same) is the V leaf's: 0 for a latent cache, which keeps ONE row a
        token and block in the K leaf and no second pool."""

        def leaf(width):
            shape = (n_layers, n_pages, n_kv_heads, page_size, width)
            if quantized:
                return {
                    "q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:-1], jnp.float32),
                }
            return jnp.zeros(shape, dtype)

        pool = cls(
            k=leaf(d_head),
            v=leaf(d_head if d_head_v is None else d_head_v),
            page_size=page_size,
            _free=list(range(n_pages)),
            dp_shards=max(1, int(dp_shards)),
        )
        _publish_pool_gauges(pool._free, n_pages)
        return pool

    def shard_of(self, page: int) -> int:
        """dp shard owning ``page`` (contiguous equal ranges)."""
        if self.dp_shards <= 1:
            return 0
        return min(
            page // max(1, self.n_pages // self.dp_shards),
            self.dp_shards - 1,
        )

    def free_pages_in(self, shard: int) -> int:
        """Free pages inside one dp shard's range."""
        if self.dp_shards <= 1:
            return len(self._free)
        return sum(1 for p in self._free if self.shard_of(p) == shard)

    @property
    def quantized(self) -> bool:
        return isinstance(self.k, dict)

    @property
    def n_pages(self) -> int:
        return _codes(self.k).shape[1]

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by MORE than one reader — the
        ``llm_prefix_shared_pages`` gauge's definition."""
        return sum(1 for c in self._refs.values() if c >= 2)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def payload_nbytes(self) -> int:
        """Total bytes of the pool's K+V payload (int8 pools count codes
        AND per-position scales) — the global figure the sharded
        session's per-device accounting divides by its mesh placement."""
        total = 0
        for leaf in (self.k, self.v):
            parts = leaf.values() if isinstance(leaf, dict) else (leaf,)
            total += sum(int(arr.nbytes) for arr in parts)
        return total

    def debug_state(self) -> dict:
        """JSON-able pool snapshot for ``GET /debug/state`` (same
        definitions as the gauges — see :func:`_fragmentation`)."""
        total = self.n_pages
        return {
            "pages": total,
            "free_pages": len(self._free),
            "page_size": self.page_size,
            "quantized": self.quantized,
            "occupancy": round(
                1.0 - len(self._free) / total if total else 0.0, 4
            ),
            "fragmentation": round(_fragmentation(self._free), 4),
            "shared_pages": self.shared_pages,
            "payload_bytes": self.payload_nbytes(),
            "dp_shards": self.dp_shards,
        }

    def alloc(
        self, n_pages: int, shard: "Optional[int]" = None
    ) -> List[int]:
        if n_pages > len(self._free):
            _POOL_EXHAUSTED.inc()
            FLIGHT.emit(
                EV_POOL_EXHAUSTED,
                needed=n_pages,
                free=len(self._free),
                total=self.n_pages,
            )
            raise PagePoolExhausted(
                f"need {n_pages} pages, {len(self._free)} free of "
                f"{self.n_pages} — evict a finished request or grow the pool"
            )
        if shard is None or self.dp_shards <= 1:
            # FIFO off the list head — the pre-dp behaviour, bit-exact.
            pages, self._free = self._free[:n_pages], self._free[n_pages:]
        else:
            # Prefer the shard's own range, spill into any free page when
            # the range is short; free-list order is preserved for the
            # pages that stay.
            pages = [p for p in self._free if self.shard_of(p) == shard][
                :n_pages
            ]
            if len(pages) < n_pages:
                taken = set(pages)
                pages += [p for p in self._free if p not in taken][
                    : n_pages - len(pages)
                ]
            taken = set(pages)
            self._free = [p for p in self._free if p not in taken]
        for p in pages:
            self._refs[p] = 1
        _publish_pool_gauges(self._free, self.n_pages, self.shared_pages)
        return pages

    def try_alloc(
        self, n_pages: int, shard: "Optional[int]" = None
    ) -> "Optional[List[int]]":
        """``alloc`` that returns ``None`` instead of raising when the
        pool is short — the admission-probe path (a continuous-batching
        join that doesn't fit should be deferred, not failed)."""
        if n_pages > len(self._free):
            return None
        return self.alloc(n_pages, shard=shard)

    def share(self, pages: List[int]) -> None:
        """Add one reader to each page (shared-prefix mapping): the page
        now recycles only after every holder calls :meth:`free` once."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"page {p} is not allocated — cannot share a free page"
                )
            self._refs[p] += 1
        _publish_pool_gauges(self._free, self.n_pages, self.shared_pages)

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page; pages whose last reader left
        return to the free list. Double-free (a page already free) is a
        bookkeeping bug and raises rather than corrupting the pool."""
        for p in pages:
            refs = self._refs.get(p)
            if refs is None:
                raise ValueError(f"page {p} is already free (double free)")
            if refs > 1:
                self._refs[p] = refs - 1
            else:
                del self._refs[p]
                self._free.append(p)
        _publish_pool_gauges(self._free, self.n_pages, self.shared_pages)

    # -- preemption page swap (ISSUE 11) ---------------------------------------
    def swap_out(self, pages: List[int]) -> PageSwapBlob:
        """Spill ``pages``' payload to host memory and free them: the
        device→host half of preemption-by-swap. REFCOUNT-AWARE by
        refusal — a shared page (refcount > 1) has other live readers
        whose content must stay device-resident, so callers release
        (``free``) shared pages and swap only exclusively-owned ones;
        passing a shared page here is a bookkeeping bug and raises.
        The free count rises by exactly ``len(pages)`` (the bytes the
        scheduler preempted FOR); :meth:`swap_in` restores it exactly.
        """
        for p in pages:
            refs = self._refs.get(p)
            if refs is None:
                raise ValueError(f"page {p} is free — cannot swap it out")
            if refs > 1:
                raise ValueError(
                    f"page {p} is shared (refcount {refs}) — shared CoW "
                    "prefix pages are released, never swapped"
                )
        idx = jnp.asarray(pages, jnp.int32)

        def gather(pool):
            if isinstance(pool, dict):
                return {
                    # [L, N, ...] → scatter_pages' [N, L, ...] chunk layout
                    "q": jax.device_get(
                        pool["q"][:, idx].transpose(1, 0, 2, 3, 4)
                    ),
                    "s": jax.device_get(
                        pool["s"][:, idx].transpose(1, 0, 2, 3)
                    ),
                }
            return jax.device_get(pool[:, idx].transpose(1, 0, 2, 3, 4))

        k_chunks = gather(self.k)
        v_chunks = gather(self.v)
        nbytes = 0
        for chunks in (k_chunks, v_chunks):
            parts = (
                chunks.values() if isinstance(chunks, dict) else (chunks,)
            )
            nbytes += sum(int(a.nbytes) for a in parts)
        self.free(pages)
        observe_swap("out", nbytes)
        return PageSwapBlob(
            k_chunks=k_chunks,
            v_chunks=v_chunks,
            n_pages=len(pages),
            page_size=self.page_size,
            quantized=self.quantized,
            nbytes=nbytes,
        )

    def swap_in(
        self, blob: PageSwapBlob, pages: "Optional[List[int]]" = None
    ) -> List[int]:
        """Restore a swapped blob into the pool (host→device): allocate
        ``blob.n_pages`` fresh pages — or scatter into ``pages`` the
        caller already reserved (resume reservations are taken at
        ``resume_begin`` so concurrent joiners cannot oversubscribe) —
        and write the payload back bit-exactly (int8 blobs carry codes
        AND per-position scales, so no requantization happens). Returns
        the page list, in blob chunk order."""
        if blob.quantized != self.quantized or blob.page_size != self.page_size:
            raise ValueError(
                "swap blob does not match this pool's layout "
                f"(page_size {blob.page_size} vs {self.page_size}, "
                f"quantized {blob.quantized} vs {self.quantized})"
            )
        if pages is None:
            pages = self.alloc(blob.n_pages)
        elif len(pages) != blob.n_pages:
            raise ValueError(
                f"resume reserved {len(pages)} pages for a "
                f"{blob.n_pages}-page blob"
            )
        self.k, self.v = scatter_pages(
            self.k,
            self.v,
            jnp.asarray(pages, jnp.int32),
            jax.tree.map(jnp.asarray, blob.k_chunks),
            jax.tree.map(jnp.asarray, blob.v_chunks),
        )
        observe_swap("in", blob.nbytes)
        return list(pages)


def page_slot(table, lengths, page_size: int):
    """THE page-table addressing rule, defined once: token number ``n`` of
    a request lives at ``(table[n // page_size], n % page_size)``.

    ``table`` [..., Jmax] and ``lengths`` [...] broadcast: a single row +
    scalar gives scalars; a [B, Jmax] table + [B] lengths gives per-row
    (pages, slots). Every writer — the transformer's decode append and the
    helpers here — routes through this function so the arithmetic cannot
    drift between implementations.
    """
    lengths = jnp.asarray(lengths, jnp.int32)
    pages = jnp.take_along_axis(
        jnp.asarray(table, jnp.int32),
        (lengths // page_size)[..., None],
        axis=-1,
    )[..., 0]
    return pages, lengths % page_size


def write_token(
    pool_k: "jnp.ndarray | dict",  # [L, P, Hkv, page, D] — or {"q","s"}
    pool_v: "jnp.ndarray | dict",
    page_table_row: jnp.ndarray,  # [Jmax] int32 — ONE request's pages
    length: jnp.ndarray,  # scalar int32: tokens already written
    k_vec: jnp.ndarray,  # [L, Hkv, D] — this token's K across layers
    v_vec: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Append one token's K/V for one request (jit-safe, static shapes).

    Single-row convenience over :func:`page_slot`; the engine's batched
    decode loop does the same addressing per row inside
    ``models/transformer._attention_block`` (also via :func:`page_slot`).
    Quantized pools quantize the vector with the decode-step scale math
    (models/quantize.quantize_kv_vector) and write codes + scale.
    """
    page_size = _codes(pool_k).shape[3]
    page, slot = page_slot(page_table_row, length, page_size)

    def write(pool, vec):
        if isinstance(pool, dict):
            from ..models.quantize import quantize_kv_vector

            q, s = quantize_kv_vector(vec)  # [L,Hkv,D] int8, [L,Hkv] f32
            return {
                "q": jax.lax.dynamic_update_slice(
                    pool["q"], q[:, None, :, None, :], (0, page, 0, slot, 0)
                ),
                "s": jax.lax.dynamic_update_slice(
                    pool["s"], s[:, None, :, None], (0, page, 0, slot)
                ),
            }
        # [L, Hkv, D] → [L, 1, Hkv, 1, D] at (layer 0, page, head 0, slot, 0)
        return jax.lax.dynamic_update_slice(
            pool, vec[:, None, :, None, :].astype(pool.dtype),
            (0, page, 0, slot, 0),
        )

    return write(pool_k, k_vec), write(pool_v, v_vec)


def pool_widths(cfg, stacked: bool) -> Tuple[int, int]:
    """Row widths of the pool's K and V leaves for ``cfg``'s cache
    (``ModelConfig.cache_k_width`` / ``cache_v_width``): as they are, or
    in stacked-hybrid mode rounded up to the 128 lanes the parts paths
    read (phi3's 96 -> 128, a latent row's 576 -> 640; a latent cache's
    zero-width V leaf stays 0)."""
    def lanes(width: int) -> int:
        return -(-width // 128) * 128 if stacked else width

    return lanes(cfg.cache_k_width), lanes(cfg.cache_v_width)


def pages_pinned(
    prompt_len: int, max_new_tokens: int, page_size: int, stacked: bool
) -> int:
    """Pool pages ONE row pins for its whole life: the prompt's in
    stacked-hybrid mode (generated tokens live in the side caches), the
    prompt's and the budget's where decode writes into the pool. Plain
    and speculative rows alike: verify candidates live in the side
    caches / scratch leaves, never in pool slots past the budget. The
    session allocates by it; the two row estimators of the engine bill
    by it."""
    if stacked:
        return -(-max(prompt_len, 1) // page_size)
    return -(-(prompt_len + max_new_tokens) // page_size)


def side_rows(lead: Tuple[int, ...], width: int, dtype, quantized: bool):
    """A stacked-hybrid side cache leaf of zeros, ``lead + (width,)``:
    one row a generated token and attention block, ``{"q", "s"}`` codes
    and per-row scales under int8 KV."""
    if quantized:
        return {
            "q": jnp.zeros(lead + (width,), jnp.int8),
            "s": jnp.zeros(lead, jnp.float32),
        }
    return jnp.zeros(lead + (width,), dtype=dtype)


def pad_to_pool(ck: jnp.ndarray, cv: jnp.ndarray, widths: Tuple[int, int]):
    """Zero-pad paginated K and V chunks' last axis to the pool leaves'."""
    out = []
    for chunk, width in zip((ck, cv), widths):
        if chunk.shape[-1] != width:
            chunk = jnp.pad(
                chunk,
                [(0, 0)] * (chunk.ndim - 1) + [(0, width - chunk.shape[-1])],
            )
        out.append(chunk)
    return out[0], out[1]


def _paginate(seq: jnp.ndarray, s_real: int, page_size: int) -> jnp.ndarray:
    """[L, Hkv, S, D] contiguous slab → [n_pages, L, Hkv, page, D] chunks
    (tail page zero-padded). Row-sized ops only — no pool copies."""
    n_pages = -(-s_real // page_size)
    seq = seq[:, :, :s_real]
    pad = n_pages * page_size - s_real
    if pad:
        seq = jnp.pad(seq, ((0, 0), (0, 0), (0, pad), (0, 0)))
    l, hkv, _, d = seq.shape
    # [L, Hkv, n·page, D] → [n, L, Hkv, page, D]
    return seq.reshape(l, hkv, n_pages, page_size, d).transpose(2, 0, 1, 3, 4)


def quantize_chunks(
    k_chunks: jnp.ndarray,  # [N, L, Hkv, page, D] bf16/f32
    v_chunks: jnp.ndarray,
    levels=KV_INT8_LEVELS,  # quantize_kv_vector's: traced inside a program
) -> Tuple[dict, dict]:
    """Per-position int8 quantization of page chunks, for scattering
    into a quantized pool: ``{"q": int8 [N,L,Hkv,page,D], "s": f32
    [N,L,Hkv,page]}``. Routes through ``quantize_kv_vector`` — the ONE
    source of the scale math — so every real position's codes/scale are
    bit-identical to the contiguous int8 path's bulk quantization of the
    same vectors (tail-page padding quantizes to zero codes at the
    epsilon scale; attention masks those positions by real lengths)."""
    kq, ks = quantize_kv_vector(k_chunks, levels)
    vq, vs = quantize_kv_vector(v_chunks, levels)
    return {"q": kq, "s": ks}, {"q": vq, "s": vs}


def scatter_pages(
    pool_k: "jnp.ndarray | dict",  # [L, P, Hkv, page, D] — or {"q","s"}
    pool_v: "jnp.ndarray | dict",
    page_indices: jnp.ndarray,  # [N] int32 — destination pool pages
    k_chunks: "jnp.ndarray | dict",  # [N, L, Hkv, page, D] — or {"q","s"}
    v_chunks: "jnp.ndarray | dict",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write N pages into the pool in ONE scatter per pool leaf, instead
    of one ``dynamic_update_slice`` per page. Called eagerly (batch
    assembly, a session's open, the prefix store) each scatter makes one
    full copy of its leaf, O(1) copies however many pages the batch
    holds; traced inside a program that donates the pool (a session's
    row install, :func:`install_pages`) it writes the pages in place and
    copies nothing. Quantized pools take :func:`quantize_chunks` output
    and scatter codes and scales alike. A page index outside the pool is
    dropped with its chunk: that is how the row install skips the pages
    it must not write, with indices that are traced numbers."""
    idx = jnp.asarray(page_indices, jnp.int32)

    def scatter(pool, chunks):
        if isinstance(pool, dict):
            return {
                "q": pool["q"].at[:, idx].set(
                    chunks["q"].transpose(1, 0, 2, 3, 4).astype(jnp.int8),
                    mode="drop",
                ),
                "s": pool["s"].at[:, idx].set(
                    chunks["s"].transpose(1, 0, 2, 3).astype(jnp.float32),
                    mode="drop",
                ),
            }
        return pool.at[:, idx].set(
            chunks.transpose(1, 0, 2, 3, 4).astype(pool.dtype), mode="drop"
        )

    return scatter(pool_k, k_chunks), scatter(pool_v, v_chunks)


def install_pages(
    pool_k: "jnp.ndarray | dict",  # [L, P, Hkv, page, D] — or {"q","s"}
    pool_v: "jnp.ndarray | dict",
    k_cache: jnp.ndarray,  # [L, 1, Hkv, alloc, D] — a private prefill cache
    v_cache: jnp.ndarray,  # a latent cache's V leaf is zero wide
    s_real,  # int32 scalar, traced: positions the prefill really wrote
    dest: jnp.ndarray,  # [ceil(alloc / page)] int32 — destination pool pages
    levels=KV_INT8_LEVELS,  # float32 scalar, traced: quantize_kv_vector's
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The pool with one row's private prefill cache written into its
    pages: the traceable body of a session's row-install program
    (engine/stepped.py). Only shapes are static — the cache paginates to
    its whole ``alloc`` whatever ``s_real`` is, so one executable serves
    every prompt length of a bucket. Positions at or past ``s_real``
    become zero, which makes the tail page exactly what
    :func:`_paginate` builds from a slice and a pad; pages wholly past
    ``s_real`` and pages the row must not write (a shared prefix's, the
    store's) carry an index outside the pool in ``dest`` and are dropped
    by :func:`scatter_pages`. Chunks widen to the pool's lanes and, for
    a ``{"q","s"}`` pool, quantize through :func:`quantize_chunks`, with
    ``levels`` a runtime 127 so that the scales are the eager path's to
    the last bit."""
    page_size = _codes(pool_k).shape[3]
    widths = (_codes(pool_k).shape[-1], _codes(pool_v).shape[-1])
    alloc = k_cache.shape[3]
    real = (jnp.arange(alloc) < s_real)[None, None, :, None]

    def chunks(cache):
        seq = cache[:, 0]
        return _paginate(jnp.where(real, seq, 0), alloc, page_size)

    ck, cv = pad_to_pool(chunks(k_cache), chunks(v_cache), widths)
    if isinstance(pool_k, dict):
        ck, cv = quantize_chunks(ck, cv, levels)
    return scatter_pages(pool_k, pool_v, dest, ck, cv)


def write_prefill(
    pool_k: "jnp.ndarray | dict",
    pool_v: "jnp.ndarray | dict",
    page_table_row: jnp.ndarray,  # [Jmax]
    k_seq: jnp.ndarray,  # [L, Hkv, S, D] — a prefilled contiguous slab
    v_seq: jnp.ndarray,
    s_real: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one request's contiguous prefill result into its pages:
    prefill stays a dense contiguous computation — paging only changes
    where the result lives (quantized pools quantize the chunks on the
    way in). One scatter for all its pages; batch callers should
    paginate every row and make a single :func:`scatter_pages` call
    instead."""
    page_size = _codes(pool_k).shape[3]
    n_pages = -(-s_real // page_size)
    k_chunks = _paginate(k_seq, s_real, page_size)
    v_chunks = _paginate(v_seq, s_real, page_size)
    if isinstance(pool_k, dict):
        k_chunks, v_chunks = quantize_chunks(k_chunks, v_chunks)
    return scatter_pages(
        pool_k, pool_v, page_table_row[:n_pages], k_chunks, v_chunks
    )
