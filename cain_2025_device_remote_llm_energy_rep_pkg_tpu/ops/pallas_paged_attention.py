"""Pallas paged-KV flash-decode attention (BASELINE.json: "paged-KV
attention").

The contiguous decode kernel (``pallas_attention.py``) requires each
request's cache to be one [Hkv, T, D] slab, so a batch must allocate every
row at the widest shape. Paged attention breaks the cache into fixed-size
**pages** held in one shared pool:

  k_pool, v_pool: [P, Hkv, page, D]   — P pages shared by all requests
  page_table:     [B, Jmax] int32     — request b's j-th page index
  lengths:        [B] int32           — valid tokens per request

so a request holds exactly ``ceil(len/page)`` pages and mixed-length
concurrent requests waste no HBM on padding — the reason vLLM-class
servers page their caches, rebuilt here TPU-first.

Kernel design: identical online-softmax accumulation to the contiguous
kernel (grid (B, Hkv, Jmax), page axis innermost → sequential
accumulation), but the BlockSpec index_map reads the scalar-prefetched
page table to DMA the right [page, D] tile from the pool: the indirection
costs nothing — the DMA engine is handed a different base offset per
step, there is no gather. Pages past a request's length are clamped to
its last valid page (Pallas elides the repeated DMA) and their compute is
gated off with ``pl.when``.

Two entry points share the accumulation body (``_accumulate_page``):

- :func:`pallas_paged_decode_attention` — per-layer pools, normalised
  output (the batched-decode legacy path and the TP gather-fallback's
  kernel counterpart).
- :func:`pallas_paged_decode_attention_parts` — emits the UNNORMALISED
  (acc, m, l) triplet over the cached tokens for the stacked-hybrid
  decode loop's side-cache merge (models/transformer.py; measured
  rationale in docs/PERF.md "paged batched decode"). Default/shipped
  mode takes per-layer [P, Hkv, page, Dp] pools (the decode scan
  streams the read-only pool as xs); passing ``layer`` instead takes
  the whole [L, P, Hkv, page, Dp] stacked pool with the layer folded
  into the DMA offset.
- :func:`pallas_paged_decode_attention_parts_int8` — the same parts
  contract over an int8 page pool (codes + per-position scales,
  engine/paged_kv.py quantized mode). Dequantization never
  materialises: K's per-position scale multiplies the score column it
  produced and V's scale folds into the probability row — the identical
  trick the solo ``pallas_decode_attention_int8`` kernel uses. Scales
  ship with a trailing singleton lane dim ([..., page, 1]) for the same
  Mosaic tiling reason (the round-5 int8-KV lowering lesson).
- :func:`xla_paged_decode_attention_parts` /
  :func:`xla_paged_decode_attention_parts_int8` — the fused-XLA
  siblings of the two parts kernels (same contract), which the engine
  compiles at narrow tables
  (``engine/jax_engine.py::paged_parts_impl``). One shared body
  (``_page_parts``) scores pages in the pool's dtype and layout, each
  for one row; int8 scales fold into the score and probability columns
  as in the kernel. The pages are named by POOL INDEX (the pool read
  where it lies, each page once against its one holder's query;
  :func:`pool_page_owners` is the inverse table) or, where a page can
  have several readers, by TABLE ENTRY (every row's table pages
  gathered first). Their device time is PERF.md §5's
  ``attn.kv_gather`` + ``attn.core``.
- :func:`pallas_paged_decode_attention_mq_parts` /
  :func:`pallas_paged_decode_attention_mq_parts_int8` — MULTI-QUERY
  twins of the parts kernels (ISSUE 10): a ``[B, Q≤k+1, Hq, D]`` query
  block — the k+1 candidate positions of a speculative verify round
  (Leviathan et al. ICML 2023) — streams each row's pages ONCE and
  accumulates an online-softmax ``(acc, m, l)`` triplet per query
  position, applying the per-row per-query causal limit
  ``kpos < min(lengths[b], offsets[b] + j + 1)``. The query positions
  fold into the kernel's group dim (row ``r`` of the [Q·G, page] score
  tile is query ``r // G``), so the grid, the page streaming and the
  accumulation body are EXACTLY the single-query kernels' — at Q = 1
  the kernels reduce to them bit-for-bit. Both take the per-layer-xs
  and stacked-``layer`` pool forms and the same ``interpret=`` path, so
  CPU CI pins parity without a chip.

Parity is pinned against a gather-then-attend reference on scattered page
permutations (tests/test_paged_attention.py, tests/test_paged_int8.py,
tests/test_paged_mq.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu


def _accumulate_page(
    q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, block_start, length, scale
):
    """One page's online-softmax update — THE shared body of the
    kernels. Reshape-based K/V reads serve the per-layer block
    ([1,1,page,D]) and the stacked block ([1,1,1,page,Dp]) alike.
    ``length`` is a scalar visible-token count, or a per-score-row
    [rows, 1] limit column (the multi-query kernels' per-query causal
    cut — it broadcasts against the [rows, page] position index)."""
    q = q_ref[0, 0].astype(jnp.float32)  # [G,D]
    k = k_ref[...].reshape(k_ref.shape[-2:]).astype(jnp.float32)  # [page,D]
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [G,page]
    idx = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(idx < length, s, -jnp.inf)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[...].reshape(v_ref.shape[-2:]).astype(jnp.float32)  # [page,D]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _last_valid_page(j, b_i, lens, page: int):
    """Clamp page index ``j`` to the request's frontier page — Pallas
    elides the repeated DMA when the block index repeats, so skipped
    iterations stream nothing from HBM."""
    last_j = jnp.maximum((lens[b_i] - 1) // page, 0)
    return jnp.minimum(j, last_j)


def _paged_decode_kernel(
    page_table_ref,  # SMEM [B, Jmax] int32 (scalar-prefetched)
    lengths_ref,  # SMEM [B] int32 (scalar-prefetched)
    q_ref,  # VMEM [1, 1, G, D]
    k_ref,  # VMEM [1, 1, page, D] — the page named by the table
    v_ref,  # VMEM [1, 1, page, D]
    o_ref,  # VMEM [1, 1, G, D]
    m_ref,  # VMEM scratch [G, 128] f32
    l_ref,  # VMEM scratch [G, 128] f32
    acc_ref,  # VMEM scratch [G, D] f32
    *,
    page: int,
    n_pages_per_req: int,
    scale: float,
):
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    length = lengths_ref[b_i]
    block_start = j * page

    @pl.when(block_start < length)
    def _block():
        _accumulate_page(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            block_start, length, scale,
        )

    @pl.when(j == n_pages_per_req - 1)
    def _finalise():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _paged_decode_parts_kernel(
    page_table_ref,
    lengths_ref,
    _layer_ref,  # consumed by the index maps
    q_ref,
    k_ref,  # VMEM [1, 1, 1, page, Dp] — stacked pool block
    v_ref,
    acc_out_ref,  # VMEM [1, 1, G, Dp] f32 — UNNORMALISED sum e^{s-m}·v
    m_out_ref,  # VMEM [1, 1, G, 128] f32 — running max
    l_out_ref,  # VMEM [1, 1, G, 128] f32 — sum e^{s-m}
    m_ref,
    l_ref,
    acc_ref,
    *,
    page: int,
    n_pages_per_req: int,
    scale: float,
):
    """Stacked-pool variant: same accumulation, raw (acc, m, l) out —
    the caller merges the current token's self-attention term
    analytically, which is what lets the decode loop defer every pool
    write to one batched scatter per step. A zero-length row exits with
    (0, -inf, 0), which the merge maps to pure self-attention."""
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    length = lengths_ref[b_i]
    block_start = j * page

    @pl.when(block_start < length)
    def _block():
        _accumulate_page(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            block_start, length, scale,
        )

    @pl.when(j == n_pages_per_req - 1)
    def _emit():
        acc_out_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _accumulate_page_int8(
    q_ref, k_ref, ks_ref, v_ref, vs_ref, m_ref, l_ref, acc_ref,
    block_start, length, scale,
):
    """One int8 page's online-softmax update: K's per-position scale is
    applied to the score COLUMN it produced (scales commute with the q·k
    dot over D) and V's scale folds into the probability row before the
    p·v dot — two [G,page] multiplies instead of a [page,D] dequant.
    Reshapes serve the per-layer ([1,1,page,Dp]) and stacked
    ([1,1,1,page,Dp]) blocks alike; scales ride a trailing singleton
    lane dim (see the module docstring). ``length`` may be a per-row
    [rows, 1] limit column like :func:`_accumulate_page`'s."""
    q = q_ref[0, 0].astype(jnp.float32)  # [G,D]
    k = k_ref[...].reshape(k_ref.shape[-2:]).astype(jnp.float32)  # codes
    ks = ks_ref[...].reshape(ks_ref.shape[-2:])[:, 0].astype(jnp.float32)
    vs = vs_ref[...].reshape(vs_ref.shape[-2:])[:, 0].astype(jnp.float32)
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
        * ks[None, :]
    )  # [G,page]
    idx = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(idx < length, s, -jnp.inf)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[...].reshape(v_ref.shape[-2:]).astype(jnp.float32)  # codes
    pv = jax.lax.dot_general(
        p * vs[None, :],  # v dequant folded into the probability row
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _paged_decode_parts_int8_kernel(
    page_table_ref,
    lengths_ref,
    _layer_ref,  # consumed by the index maps
    q_ref,
    k_ref,  # VMEM [1, 1, (1,) page, Dp] int8 codes
    ks_ref,  # VMEM [1, 1, (1,) page, 1] f32 per-position K scales
    v_ref,
    vs_ref,
    acc_out_ref,
    m_out_ref,
    l_out_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page: int,
    n_pages_per_req: int,
    scale: float,
):
    """Int8 twin of :func:`_paged_decode_parts_kernel`: same grid, same
    (acc, m, l) contract, codes+scales instead of bf16 pages."""
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    length = lengths_ref[b_i]
    block_start = j * page

    @pl.when(block_start < length)
    def _block():
        _accumulate_page_int8(
            q_ref, k_ref, ks_ref, v_ref, vs_ref, m_ref, l_ref, acc_ref,
            block_start, length, scale,
        )

    @pl.when(j == n_pages_per_req - 1)
    def _emit():
        acc_out_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _mq_limit(q_rows: int, group: int, length, offset):
    """Per-score-row visible-token limit of a multi-query block: row
    ``r`` is query position ``r // group``, which sees cached tokens
    ``kpos < length`` under the causal cut ``kpos <= offset + r//group``
    — one [Q·G, 1] column the accumulation bodies broadcast against
    their [Q·G, page] position index, turning the single-query kernels
    multi-query without touching their math."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_rows, 1), 0) // group
    return jnp.minimum(length, offset + qi + 1)


def _paged_decode_mq_parts_kernel(
    page_table_ref,
    lengths_ref,
    offsets_ref,  # SMEM [B] int32 — query position 0 of each row
    _layer_ref,  # consumed by the index maps
    q_ref,  # VMEM [1, 1, Q·G, Dp]
    k_ref,  # VMEM [1, 1, (1,) page, Dp]
    v_ref,
    acc_out_ref,  # VMEM [1, 1, Q·G, Dp] f32
    m_out_ref,  # VMEM [1, 1, Q·G, 128] f32
    l_out_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page: int,
    n_pages_per_req: int,
    scale: float,
    group: int,
):
    """Multi-query twin of :func:`_paged_decode_parts_kernel`: the query
    positions ride the group dim, so the page loop streams each row's
    pages ONCE for all Q positions; only the mask column differs per
    score row (``_mq_limit``). At Q = 1 the limit column collapses to
    the scalar ``length`` and this IS the single-query kernel."""
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    length = lengths_ref[b_i]
    limit = _mq_limit(m_ref.shape[0], group, length, offsets_ref[b_i])
    block_start = j * page

    @pl.when(block_start < length)
    def _block():
        _accumulate_page(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            block_start, limit, scale,
        )

    @pl.when(j == n_pages_per_req - 1)
    def _emit():
        acc_out_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _paged_decode_mq_parts_int8_kernel(
    page_table_ref,
    lengths_ref,
    offsets_ref,
    _layer_ref,
    q_ref,
    k_ref,  # int8 codes
    ks_ref,  # f32 per-position K scales [..., page, 1]
    v_ref,
    vs_ref,
    acc_out_ref,
    m_out_ref,
    l_out_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page: int,
    n_pages_per_req: int,
    scale: float,
    group: int,
):
    """Int8 multi-query twin: same per-row limit column, scales folded
    into the softmax exactly as the single-query int8 kernel."""
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    length = lengths_ref[b_i]
    limit = _mq_limit(m_ref.shape[0], group, length, offsets_ref[b_i])
    block_start = j * page

    @pl.when(block_start < length)
    def _block():
        _accumulate_page_int8(
            q_ref, k_ref, ks_ref, v_ref, vs_ref, m_ref, l_ref, acc_ref,
            block_start, limit, scale,
        )

    @pl.when(j == n_pages_per_req - 1)
    def _emit():
        acc_out_ref[0, 0] = acc_ref[...]
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _mq_parts_call(
    q,  # [B, Q, Hq, D]
    pools,  # (k_pool, v_pool) or (k_pool, ks, v_pool, vs)
    page_table,
    lengths,
    offsets,
    *,
    layer,
    interpret,
    int8: bool,
):
    """Shared pallas_call plumbing of the two multi-query entry points:
    fold Q into the group dim, run the (B, Hkv, Jmax) grid, unfold the
    outputs back to per-query-position triplets."""
    b, qlen, hq, d = q.shape
    stacked = layer is not None
    codes = pools[0]
    if stacked:
        _, n_pool, hkv, page, dp = codes.shape
    else:
        n_pool, hkv, page, dp = codes.shape
    if dp % 128:
        raise ValueError(
            f"pools must be pre-padded to a 128-multiple head "
            f"dim, got {dp} (per-call padding would copy the pool)"
        )
    d_pad = dp - d
    jmax = page_table.shape[1]
    group = hq // hkv
    qg = qlen * group
    scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()

    # [B, Q, Hkv, G, D] → [B, Hkv, Q·G, D]: query positions become the
    # slow half of the group dim (score row r ↔ query r // G)
    qr = q.reshape(b, qlen, hkv, group, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, qg, d)
    if d_pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pool - 1)

    base_kernel = functools.partial(
        _paged_decode_mq_parts_int8_kernel if int8
        else _paged_decode_mq_parts_kernel,
        page=page,
        n_pages_per_req=jmax,
        scale=scale,
        group=group,
    )

    if stacked:
        kernel = base_kernel
        num_prefetch = 4
        prefetch_args = (
            table,
            lengths.astype(jnp.int32),
            offsets.astype(jnp.int32),
            jnp.reshape(layer, (1,)).astype(jnp.int32),
        )

        def q_index(b_i, h, j, tab, lens, offs, lay):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens, offs, lay):
            return (
                lay[0],
                tab[b_i, _last_valid_page(j, b_i, lens, page)],
                h,
                0,
                0,
            )

        kv_block = (1, 1, 1, page, dp)
        scale_block = (1, 1, 1, page, 1)
    else:
        def kernel(table_ref, lengths_ref, offsets_ref, *rest):
            return base_kernel(table_ref, lengths_ref, offsets_ref, None, *rest)

        num_prefetch = 3
        prefetch_args = (
            table,
            lengths.astype(jnp.int32),
            offsets.astype(jnp.int32),
        )

        def q_index(b_i, h, j, tab, lens, offs):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens, offs):
            return (tab[b_i, _last_valid_page(j, b_i, lens, page)], h, 0, 0)

        kv_block = (1, 1, page, dp)
        scale_block = (1, 1, page, 1)

    if int8:
        k_pool, ks, v_pool, vs = pools
        in_specs = [
            pl.BlockSpec((1, 1, qg, dp), q_index),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(scale_block, kv_index),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(scale_block, kv_index),
        ]
        operands = (qr, k_pool, ks, v_pool, vs)
    else:
        k_pool, v_pool = pools
        in_specs = [
            pl.BlockSpec((1, 1, qg, dp), q_index),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(kv_block, kv_index),
        ]
        operands = (qr, k_pool, v_pool)

    acc, m, l = pl.pallas_call(
        kernel,
        # the entry point's own name, as the two public wrappers have it
        name="pallas_paged_decode_attention_mq_parts"
        + ("_int8" if int8 else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_prefetch,
            grid=(b, hkv, jmax),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, qg, dp), q_index),
                pl.BlockSpec((1, 1, qg, 128), q_index),
                pl.BlockSpec((1, 1, qg, 128), q_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((qg, 128), jnp.float32),
                pltpu.VMEM((qg, 128), jnp.float32),
                pltpu.VMEM((qg, dp), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, qg, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, qg, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, qg, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch_args, *operands)
    if d_pad:
        acc = acc[..., :d]
    # [B, Hkv, Q·G, …] → per-query-position [B, Q, Hkv, G, …]
    acc = acc.reshape(b, hkv, qlen, group, d).transpose(0, 2, 1, 3, 4)
    m = m[..., 0].reshape(b, hkv, qlen, group).transpose(0, 2, 1, 3)
    l = l[..., 0].reshape(b, hkv, qlen, group).transpose(0, 2, 1, 3)
    return acc, m, l


def pallas_paged_decode_attention_mq_parts(
    q: jnp.ndarray,  # [B, Q, Hq, D] — Q candidate positions per row
    k_pool: jnp.ndarray,  # [P, Hkv, page, Dp] — or [L, P, ...] with layer
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32 — CACHED tokens (candidates excluded)
    offsets: jnp.ndarray,  # [B] int32 — absolute position of query 0
    *,
    layer: Optional[jnp.ndarray] = None,  # scalar int32: stacked pools
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Multi-query unnormalised flash-decode parts over the cached
    tokens of a pool — the speculative-verify twin of
    :func:`pallas_paged_decode_attention_parts` (ISSUE 10): one pass
    streams each row's pages once for all ``Q ≤ k+1`` candidate
    positions and returns ``(acc [B,Q,Hkv,G,D] f32, m [B,Q,Hkv,G], l
    [B,Q,Hkv,G])``, each query position masked by the per-row causal
    cut ``kpos < min(lengths[b], offsets[b] + j + 1)``. The caller
    merges the candidates' own K/V (side cache / scratch — they never
    touch the pool during verify) through the standard online-softmax
    part merge. Same per-layer-xs vs stacked-``layer`` duality and
    pre-padded-Dp requirement as the single-query parts kernel; at
    Q = 1 the two are identical."""
    return _mq_parts_call(
        q, (k_pool, v_pool), page_table, lengths, offsets,
        layer=layer, interpret=interpret, int8=False,
    )


def pallas_paged_decode_attention_mq_parts_int8(
    q: jnp.ndarray,  # [B, Q, Hq, D]
    k_pool: jnp.ndarray,  # int8 codes [P, Hkv, page, Dp] — or [L, P, ...]
    k_scale: jnp.ndarray,  # f32 [P, Hkv, page] — or [L, P, Hkv, page]
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32
    offsets: jnp.ndarray,  # [B] int32
    *,
    layer: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Multi-query int8 parts — the quantized twin of
    :func:`pallas_paged_decode_attention_mq_parts`, math-identical to
    running it on the dequantized pool (K's per-position scale
    multiplies its score column, V's folds into the probability row —
    the single-query int8 kernel's trick, unchanged). Scales ship with
    the trailing singleton lane dim for the same Mosaic tiling reason."""
    ks = k_scale.astype(jnp.float32)[..., None]
    vs = v_scale.astype(jnp.float32)[..., None]
    return _mq_parts_call(
        q, (k_pool, ks, v_pool, vs), page_table, lengths, offsets,
        layer=layer, interpret=interpret, int8=True,
    )


def paged_mq_attention_reference(
    q: jnp.ndarray,  # [B, Q, Hq, D]
    k_pool: jnp.ndarray,  # [P, Hkv, page, D] (bf16/f32 — dequantized)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    offsets: jnp.ndarray,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """jnp reference for the multi-query parts contract: gather the
    pages, dense per-query-masked score/softmax parts — used only to
    pin the MQ kernels' numerics (tests/test_paged_mq.py)."""
    b, qlen, hq, d = q.shape
    _, hkv, page, _ = k_pool.shape
    jmax = page_table.shape[1]
    t = jmax * page
    group = hq // hkv
    table = jnp.clip(page_table.astype(jnp.int32), 0, k_pool.shape[0] - 1)
    kf = k_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, t, d)
    vf = v_pool[table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, t, d)
    qg = q.reshape(b, qlen, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum(
        "bskgd,bktd->bskgt", qg, kf.astype(jnp.float32)
    ) / math.sqrt(d)
    kpos = jnp.arange(t)
    limit = jnp.minimum(
        lengths[:, None],
        offsets[:, None] + jnp.arange(qlen)[None, :] + 1,
    )  # [B, Q]
    mask = kpos[None, None, :] < limit[..., None]  # [B, Q, T]
    scores = jnp.where(mask[:, :, None, None], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bskgt,bktd->bskgd", p, vf.astype(jnp.float32))
    return acc, m, l


def pallas_paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pool: jnp.ndarray,  # [P, Hkv, page, D]
    v_pool: jnp.ndarray,  # [P, Hkv, page, D]
    page_table: jnp.ndarray,  # [B, Jmax] int32 — pool page per request block
    lengths: jnp.ndarray,  # [B] int32
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash-decode attention reading K/V through a page table.

    Semantically equal to gathering each request's pages into a contiguous
    [B, Hkv, Jmax·page, D] cache and running the contiguous decode kernel
    — without materialising that gather.
    """
    b, hq, d = q.shape
    n_pool, hkv, page, _ = k_pool.shape
    jmax = page_table.shape[1]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)

    if interpret is None:
        interpret = not on_tpu()

    d_pad = (-d) % 128
    qr = q.reshape(b, hkv, group, d)
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        qr = jnp.pad(qr, pad4)
        k_pool = jnp.pad(k_pool, pad4)
        v_pool = jnp.pad(v_pool, pad4)
    dp = d + d_pad

    # Every table entry the index_map can read must name a valid pool page
    # (slots past a request's length are clamped again below).
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pool - 1)

    kernel = functools.partial(
        _paged_decode_kernel,
        page=page,
        n_pages_per_req=jmax,
        scale=scale,
    )

    def kv_index(b_i, h, j, tab, lens):
        return (tab[b_i, _last_valid_page(j, b_i, lens, page)], h, 0, 0)

    out = pl.pallas_call(
        kernel,
        name="pallas_paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, jmax),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, dp),
                    lambda b_i, h, j, tab, lens: (b_i, h, 0, 0),
                ),
                pl.BlockSpec((1, 1, page, dp), kv_index),
                pl.BlockSpec((1, 1, page, dp), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, dp),
                lambda b_i, h, j, tab, lens: (b_i, h, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dp), q.dtype),
        interpret=interpret,
    )(table, lengths.astype(jnp.int32), qr, k_pool, v_pool)

    if d_pad:
        out = out[..., :d]
    return out.reshape(b, hq, d)


def pallas_paged_decode_attention_parts(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pool: jnp.ndarray,  # [P, Hkv, page, Dp] — or [L, P, ...] with layer
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32 — CACHED tokens (current excluded)
    *,
    layer: Optional[jnp.ndarray] = None,  # scalar int32: stacked pools
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Unnormalised flash-decode parts over the cached tokens of a pool:
    returns ``(acc [B,Hkv,G,D] f32, m [B,Hkv,G] f32, l [B,Hkv,G] f32)``
    for the caller's self/side-term merge.

    Without ``layer`` the pool is a per-layer slice [P,Hkv,page,Dp] (the
    decode scan streams the read-only pool as xs, letting XLA pipeline
    it with the weight stream); with ``layer`` the whole stacked pool is
    passed and the index map folds the layer into the DMA offset. Pools
    must be pre-padded to a 128-multiple head dim either way (the engine
    allocates them so); per-call padding would copy the pool.
    """
    b, hq, d = q.shape
    stacked = layer is not None
    if stacked:
        _, n_pool, hkv, page, dp = k_pool.shape
    else:
        n_pool, hkv, page, dp = k_pool.shape
    if dp % 128:
        raise ValueError(
            f"pools must be pre-padded to a 128-multiple head "
            f"dim, got {dp} (per-call padding would copy the pool)"
        )
    d_pad = dp - d
    jmax = page_table.shape[1]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()

    qr = q.reshape(b, hkv, group, d)
    if d_pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pool - 1)

    base_kernel = functools.partial(
        _paged_decode_parts_kernel,
        page=page,
        n_pages_per_req=jmax,
        scale=scale,
    )

    if stacked:
        kernel = base_kernel
        num_prefetch = 3
        prefetch_args = (
            table,
            lengths.astype(jnp.int32),
            jnp.reshape(layer, (1,)).astype(jnp.int32),
        )

        def q_index(b_i, h, j, tab, lens, lay):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens, lay):
            return (
                lay[0],
                tab[b_i, _last_valid_page(j, b_i, lens, page)],
                h,
                0,
                0,
            )

        kv_block = (1, 1, 1, page, dp)
    else:
        # per-layer pools: same kernel body, no layer ref
        def kernel(table_ref, lengths_ref, *rest):
            return base_kernel(table_ref, lengths_ref, None, *rest)

        num_prefetch = 2
        prefetch_args = (table, lengths.astype(jnp.int32))

        def q_index(b_i, h, j, tab, lens):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens):
            return (tab[b_i, _last_valid_page(j, b_i, lens, page)], h, 0, 0)

        kv_block = (1, 1, page, dp)

    acc, m, l = pl.pallas_call(
        kernel,
        name="pallas_paged_decode_attention_parts",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_prefetch,
            grid=(b, hkv, jmax),
            in_specs=[
                pl.BlockSpec((1, 1, group, dp), q_index),
                pl.BlockSpec(kv_block, kv_index),
                pl.BlockSpec(kv_block, kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, group, dp), q_index),
                pl.BlockSpec((1, 1, group, 128), q_index),
                pl.BlockSpec((1, 1, group, 128), q_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dp), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch_args, qr, k_pool, v_pool)
    if d_pad:
        acc = acc[..., :d]
    return acc, m[..., 0], l[..., 0]


def pallas_paged_decode_attention_parts_int8(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pool: jnp.ndarray,  # int8 codes [P, Hkv, page, Dp] — or [L, P, ...]
    k_scale: jnp.ndarray,  # f32 [P, Hkv, page] — or [L, P, Hkv, page]
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32 — CACHED tokens (current excluded)
    *,
    layer: Optional[jnp.ndarray] = None,  # scalar int32: stacked pools
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Unnormalised flash-decode parts over an INT8 page pool — the
    quantized twin of :func:`pallas_paged_decode_attention_parts`, math-
    identical to running it on the dequantized pool (scales commute with
    the dots). Same ``(acc [B,Hkv,G,D] f32, m, l)`` contract, same
    per-layer-xs vs stacked-``layer`` duality, same pre-padded-Dp
    requirement (codes at the 128-lane-padded head dim; pad lanes carry
    zero codes, contributing nothing)."""
    b, hq, d = q.shape
    stacked = layer is not None
    if stacked:
        _, n_pool, hkv, page, dp = k_pool.shape
    else:
        n_pool, hkv, page, dp = k_pool.shape
    if dp % 128:
        raise ValueError(
            f"pools must be pre-padded to a 128-multiple head "
            f"dim, got {dp} (per-call padding would copy the pool)"
        )
    d_pad = dp - d
    jmax = page_table.shape[1]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()

    qr = q.reshape(b, hkv, group, d)
    if d_pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pool - 1)
    # scales ride a trailing singleton lane dim: a [..., page] block
    # would put 1 in the sublane slot over Hkv>1, which Mosaic's tiling
    # rule rejects (the round-5 int8-KV lowering bug, fixed the same way
    # in ops/pallas_attention._decode_kernel_int8)
    ks = k_scale.astype(jnp.float32)[..., None]
    vs = v_scale.astype(jnp.float32)[..., None]

    base_kernel = functools.partial(
        _paged_decode_parts_int8_kernel,
        page=page,
        n_pages_per_req=jmax,
        scale=scale,
    )

    if stacked:
        kernel = base_kernel
        num_prefetch = 3
        prefetch_args = (
            table,
            lengths.astype(jnp.int32),
            jnp.reshape(layer, (1,)).astype(jnp.int32),
        )

        def q_index(b_i, h, j, tab, lens, lay):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens, lay):
            return (
                lay[0],
                tab[b_i, _last_valid_page(j, b_i, lens, page)],
                h,
                0,
                0,
            )

        kv_block = (1, 1, 1, page, dp)
        scale_block = (1, 1, 1, page, 1)
    else:
        def kernel(table_ref, lengths_ref, *rest):
            return base_kernel(table_ref, lengths_ref, None, *rest)

        num_prefetch = 2
        prefetch_args = (table, lengths.astype(jnp.int32))

        def q_index(b_i, h, j, tab, lens):
            return (b_i, h, 0, 0)

        def kv_index(b_i, h, j, tab, lens):
            return (tab[b_i, _last_valid_page(j, b_i, lens, page)], h, 0, 0)

        kv_block = (1, 1, page, dp)
        scale_block = (1, 1, page, 1)

    acc, m, l = pl.pallas_call(
        kernel,
        name="pallas_paged_decode_attention_parts_int8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_prefetch,
            grid=(b, hkv, jmax),
            in_specs=[
                pl.BlockSpec((1, 1, group, dp), q_index),
                pl.BlockSpec(kv_block, kv_index),
                pl.BlockSpec(scale_block, kv_index),
                pl.BlockSpec(kv_block, kv_index),
                pl.BlockSpec(scale_block, kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, group, dp), q_index),
                pl.BlockSpec((1, 1, group, 128), q_index),
                pl.BlockSpec((1, 1, group, 128), q_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dp), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch_args, qr, k_pool, ks, v_pool, vs)
    if d_pad:
        acc = acc[..., :d]
    return acc, m[..., 0], l[..., 0]


def paged_decode_attention_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
) -> jnp.ndarray:
    """jnp reference: gather pages into a contiguous cache, then plain
    masked attention — the materialised gather the kernel exists to avoid;
    used only to pin its numerics."""
    b, hq, d = q.shape
    _, hkv, page, _ = k_pool.shape
    jmax = page_table.shape[1]
    group = hq // hkv
    # [B, Jmax, Hkv, page, D] → [B, Hkv, Jmax·page, D]
    k = k_pool[page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, jmax * page, d
    )
    v = v_pool[page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, jmax * page, d
    )
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, k.astype(jnp.float32))
    scores = scores / math.sqrt(d)
    mask = jnp.arange(jmax * page)[None, :] < lengths[:, None]  # [B,T]
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def xla_paged_decode_attention_parts(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pool: jnp.ndarray,  # [P, Hkv, page, Dp] — per-layer pool slice
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32 — cached (prompt) tokens
    scale: "float | None" = None,
    v_width: "int | None" = None,
    owners: "PageOwners | None" = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Fused-XLA unnormalised flash parts — the XLA sibling of
    :func:`pallas_paged_decode_attention_parts`, same return contract
    ``(acc [B,Hkv,G,D] f32, m [B,Hkv,G], l [B,Hkv,G])``.

    ``v_pool=None`` is a LATENT pool: one compressed row a token, whose
    whole width the keys are and whose first ``v_width`` columns the
    values are (one kv head, the group every query head; ``q`` is as wide
    as the row and ``acc`` comes back ``v_width`` wide). Both
    contractions read the same pages. ``scale`` replaces ``1 / sqrt(D)``
    (a latent query's own head width sets it).

    One body (:func:`_page_parts`) scores pages in the pool's dtype and
    layout (no relayout, no f32 copy, no slice of the lane padding), and
    there are two ways to NAME the pages it scores:

    - ``owners`` given (:func:`pool_page_owners`, built once a slice):
      by POOL INDEX. The pool is read where it lies, each page once,
      against the query of the one row that holds it; nothing pool-sized
      is gathered, only the query (KB). A page nobody holds is masked
      whole. Right only while a page has at most one reader.
    - ``owners=None``: by TABLE ENTRY. Every row's ``Jmax`` table pages
      are gathered from the pool (``[B·Jmax, Hkv, page, Dp]``), live row
      or not, real slot or not. A session whose pages can have several
      readers (a prefix store maps one page into several rows' tables)
      compiles this one.

    The engine picks at trace time (``engine/jax_engine.py``:
    ``paged_parts_impl`` says ``"xla"``, the session says whether pages
    are shared). Device time: PERF.md §5's ``attn.kv_gather`` and
    ``attn.core``.

    Rows with ``lengths == 0`` (empty prompt) return m = -inf, l = 0,
    acc = 0 — the caller's online-softmax merge weights them to zero.
    """
    if owners is not None:
        return _page_parts(
            q, k_pool, k_pool if v_pool is None else v_pool, lengths,
            owners, scale=scale, v_width=v_width,
        )
    with jax.named_scope("attn.kv_gather"):
        k = _gather_pages(k_pool, page_table)
        v = k if v_pool is None else _gather_pages(v_pool, page_table)
    return _page_parts(q, k, v, lengths, None, scale=scale, v_width=v_width)


class PageOwners(NamedTuple):
    """The inverse of a page table, for pages with ONE reader each:
    ``row[p]`` / ``slot[p]`` say whose ``slot``-th page pool page ``p``
    is (a page nobody holds: row 0, slot ``Jmax``, past every length),
    ``table`` is the table itself and ``mine[b, j]`` whether entry
    ``(b, j)`` is the one that holds its page."""

    row: jnp.ndarray  # [P] int32
    slot: jnp.ndarray  # [P] int32
    table: jnp.ndarray  # [B, Jmax] int32
    mine: jnp.ndarray  # [B, Jmax] bool


def pool_page_owners(page_table, lengths, n_pages: int, page: int):
    """:class:`PageOwners` of a ``[B, Jmax]`` table over a pool of
    ``n_pages``: ONE scatter of the table's REAL entries (``j·page <
    lengths[b]``), each writing ``b·Jmax + j`` at its page. An unreal
    entry (a slot past its row's prompt, every slot of a parked row of
    length 0) scatters out of bounds and is dropped. Dead rows parked on
    one page with a stale length all claim it; one wins, the others'
    ``mine`` is False and they read as empty rows. Depends on the table
    and the lengths alone: build it once a slice, not once a layer."""
    b, jmax = page_table.shape
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    entry = jnp.arange(b * jmax, dtype=jnp.int32).reshape(b, jmax)
    real = (entry % jmax) * page < lengths.astype(jnp.int32)[:, None]
    held_by = jnp.full((n_pages,), -1, jnp.int32).at[
        jnp.where(real, table, n_pages).reshape(-1)
    ].set(entry.reshape(-1), mode="drop")
    held = held_by >= 0
    return PageOwners(
        row=jnp.where(held, held_by // jmax, 0),
        slot=jnp.where(held, held_by % jmax, jmax),
        table=table,
        mine=held_by[table] == entry,
    )


def _gather_pages(pool, page_table):
    """``pool[table]`` with the table flattened: ``[B·Jmax, ...]``, the
    shape XLA:TPU's gather produces. A ``[B, Jmax, ...]`` result is that
    plus a reshape, and a reshape between the gather and its consumer
    keeps the consumer's in-fusion convert out of the fusion (compiled
    for v5e: a standalone f32 convert of all gathered pages)."""
    flat = jnp.clip(page_table.astype(jnp.int32), 0, pool.shape[0] - 1)
    return pool[flat.reshape(-1)]


def _page_parts(
    q, k, v, lengths, owners,
    k_scale=None, v_scale=None, scale=None, v_width=None,
):
    """The shared score/softmax-parts math of the XLA variants: ``q
    [B,Hq,D]`` against ``N`` pages ``k/v [N,Hkv,page,Dp]`` in their
    stored dtype and layout → the unnormalised ``(acc, m, l)`` contract,
    column ``j·page + p`` of row ``b`` visible below ``lengths[b]``.

    Each page is scored for ONE row, as that row's ``slot``-th page.
    ``owners=None``: the pages were gathered through a ``[B, Jmax]``
    table, page ``n`` is row ``n // Jmax``'s slot ``n % Jmax`` (the
    query repeats, a row's pages are a reshape). ``owners`` given: the
    pages are the pool itself, page ``p`` is ``owners.row[p]``'s slot
    ``owners.slot[p]`` (the query is gathered per page, a row's
    per-page results are fetched through the table, entries that are not
    ``mine`` left out) — a page nobody holds is scored, masked whole and
    never fetched, so what it holds (``inf`` too) reaches no result.

    Each page is one batch entry of both contractions (f32
    accumulation, operands read as stored), so neither needs the pages
    in another order; the per-page value sums ``[N,Hkv,G,Dp]`` are added
    over a row's pages afterwards. ``q`` is zero-padded from ``D`` to
    the pool's ``Dp`` lanes (the pool's padding lanes are zeros) and the
    padding comes off ``acc``, so nothing slices the pages. int8 pages
    pass their per-position ``[N,Hkv,page]`` scales: K's multiplies
    the score column it produced, V's the probability column — the
    dequantisation ``codes × scale`` without a dequantised page.
    ``scale`` (default ``1 / sqrt(D)``) and ``v_width`` (default ``D``:
    the columns of ``acc`` that are values) serve a latent pool, where
    ``v`` is ``k`` itself."""
    b, hq, d = q.shape
    n, hkv, page, dp = k.shape
    group = hq // hkv
    f32 = jnp.float32
    if owners is None:
        jmax = n // b
        slot = jnp.arange(n, dtype=jnp.int32) % jmax

        def per_page(x):  # [B, ...] -> [N, ...]
            return jnp.repeat(x, jmax, axis=0)

        def per_row(y, empty):  # [N, ...] -> [B, Jmax, ...]
            return y.reshape(b, jmax, *y.shape[1:])
    else:
        slot = owners.slot

        def per_page(x):
            with jax.named_scope("attn.kv_gather"):
                return x[owners.row]

        def per_row(y, empty):
            mine = owners.mine.reshape(owners.mine.shape + (1,) * (y.ndim - 1))
            with jax.named_scope("attn.kv_gather"):
                return jnp.where(mine, y[owners.table], empty)

    qg = jnp.pad(q.astype(f32), ((0, 0), (0, 0), (0, dp - d)))
    # one copy of a row's query per page of the row
    qg = per_page(qg.reshape(b, hkv, group, dp))
    limit = per_page(lengths.astype(jnp.int32)) - slot * page  # [N]
    scores = jax.lax.dot_general(
        qg, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=f32,
    )  # [N, Hkv, G, page]
    if k_scale is not None:
        scores = scores * k_scale[:, :, None, :]
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    mask = jnp.arange(page, dtype=jnp.int32)[None, :] < limit[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    # -inf when the row has no prompt
    m = jnp.max(per_row(jnp.max(scores, axis=3), -jnp.inf), axis=1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    # exp(-inf)=0 masks columns
    p = jnp.exp(scores - per_page(m_safe)[..., None])
    l = jnp.sum(per_row(jnp.sum(p, axis=3), 0.0), axis=1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, :]
    acc = jax.lax.dot_general(
        p, v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=f32,
    )  # [N, Hkv, G, Dp]
    acc = jnp.sum(per_row(acc, 0.0), axis=1)
    return acc[..., : d if v_width is None else v_width], m, l


def xla_paged_decode_attention_parts_int8(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pool: jnp.ndarray,  # int8 codes [P, Hkv, page, Dp]
    k_scale: jnp.ndarray,  # f32 [P, Hkv, page]
    v_pool: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, Jmax] int32
    lengths: jnp.ndarray,  # [B] int32
    owners: "PageOwners | None" = None,
) -> "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]":
    """Int8 parts through the body :func:`xla_paged_decode_attention_parts`
    runs — the XLA sibling of
    :func:`pallas_paged_decode_attention_parts_int8`. Codes and
    per-position scales are read as stored, the pool's own (``owners``)
    or the table's gathered pages, and the scales fold into the score
    and probability columns (:func:`_page_parts`), so no page is
    dequantised in HBM and the POOL stays int8-dense. No benchmark cell
    runs it (PERF.md §7, row 4): CPU parity with the kernel is what
    holds it."""
    if owners is not None:
        return _page_parts(
            q, k_pool, v_pool, lengths, owners, k_scale, v_scale
        )
    with jax.named_scope("attn.kv_gather"):
        k = _gather_pages(k_pool, page_table)
        ks = _gather_pages(k_scale, page_table)
        v = _gather_pages(v_pool, page_table)
        vs = _gather_pages(v_scale, page_table)
    return _page_parts(q, k, v, lengths, None, ks, vs)
