"""Pallas TPU flash attention: decode (1 token vs cache) and prefill.

The hot ops of generation (BASELINE.json north star: "Pallas paged-KV
attention"). Both kernels stream the cache in T-blocks ("pages") with an
online-softmax accumulator so only one [block, D] tile of K and V is
resident in VMEM at a time:

- **decode** — one query token attends the cache's valid prefix:
  grid = (B, Hkv, T/block_t), T innermost → sequential accumulation;
  per block: s = q·kᵀ (MXU, f32 acc) → masked online softmax →
  acc = acc·α + p·v; final block writes acc/l.
- **prefill** — S query tokens at positions offset..offset+S-1 attend the
  cache causally: grid = (B, Hkv, S/block_q, T/block_k), k innermost; the
  GQA group folds into the q-row dim so the MXU sees [block_q·G, block_k]
  tiles; fully-masked k-blocks (beyond the causal frontier) are skipped, so
  peak memory is O(block_q·block_k) instead of the jnp path's O(S·T) score
  materialisation. ``offset`` > 0 gives chunked prefill against a
  partially-filled cache.

Decode is HBM-bandwidth-bound (every step streams the whole cache), which is
why the cache layout keeps each head's T rows contiguous ([B,Hkv,T,D]) —
block DMAs are pure sequential bursts.

Correctness is pinned to ``ops.attention`` references (the validation
SURVEY.md §7 lists as risk #1). On non-TPU backends the kernels run in
interpret mode, so the same code paths are exercised by CPU tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu


def _pick_block_t(t: int, preferred: int = 512) -> int:
    """Largest power-of-two divisor of t, capped at ``preferred``."""
    block = 1
    while t % (block * 2) == 0 and block * 2 <= preferred:
        block *= 2
    return block


def _decode_kernel(
    lengths_ref,  # SMEM [B] int32 (scalar-prefetched)
    q_ref,  # VMEM [1,1,G,D]
    k_ref,  # VMEM [1,1,block_t,D]
    v_ref,  # VMEM [1,1,block_t,D]
    o_ref,  # VMEM [1,1,G,D]
    m_ref,  # VMEM scratch [G,128] f32 (running max, lane-replicated)
    l_ref,  # VMEM scratch [G,128] f32 (running denominator)
    acc_ref,  # VMEM scratch [G,D] f32
    *,
    block_t: int,
    n_blocks: int,
    scale: float,
):
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b_i]
    block_start = j * block_t

    @pl.when(block_start < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # [G,D]
        k = k_ref[0, 0].astype(jnp.float32)  # [Tb,D]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [G,Tb]
        idx = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < length, s, -jnp.inf)

        m_prev = m_ref[:, :1]  # [G,1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [G,Tb]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # [Tb,D]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [G,D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blocks - 1)
    def _finalise():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def pallas_decode_attention(
    q: jnp.ndarray,  # [B,Hq,D]
    k_cache: jnp.ndarray,  # [B,Hkv,T,D]
    v_cache: jnp.ndarray,  # [B,Hkv,T,D]
    lengths: jnp.ndarray,  # [B] int32
    *,
    block_t: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash-decode attention; drop-in for ``decode_attention_reference``."""
    b, hq, d = q.shape
    _, hkv, t, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)  # pre-padding head dim sets the scale

    if interpret is None:
        interpret = not on_tpu()

    # Lane-align the head dim: zero-pad D to a multiple of 128 (zeros add
    # nothing to q·k and project to zero output columns, sliced off below).
    d_pad = (-d) % 128
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q.reshape(b, hkv, group, d), pad4)
        k_cache = jnp.pad(k_cache, pad4)
        v_cache = jnp.pad(v_cache, pad4)
        dp = d + d_pad
    else:
        q = q.reshape(b, hkv, group, d)
        dp = d

    bt = min(_pick_block_t(t, block_t), t)
    n_blocks = t // bt

    kernel = functools.partial(
        _decode_kernel, block_t=bt, n_blocks=n_blocks, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        name="pallas_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, group, dp), lambda b_i, h, j, L: (b_i, h, 0, 0)),
                pl.BlockSpec((1, 1, bt, dp), lambda b_i, h, j, L: (b_i, h, j, 0)),
                pl.BlockSpec((1, 1, bt, dp), lambda b_i, h, j, L: (b_i, h, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, dp), lambda b_i, h, j, L: (b_i, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dp), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)

    if d_pad:
        out = out[..., :d]
    return out.reshape(b, hq, d)


def _decode_kernel_int8(
    lengths_ref,  # SMEM [B] int32 (scalar-prefetched)
    q_ref,  # VMEM [1,1,G,D]
    k_ref,  # VMEM [1,1,block_t,D] int8
    ks_ref,  # VMEM [1,1,block_t,1] f32 per-position K scales
    v_ref,  # VMEM [1,1,block_t,D] int8
    vs_ref,  # VMEM [1,1,block_t,1] f32 per-position V scales
    o_ref,  # VMEM [1,1,G,D]
    m_ref,  # VMEM scratch [G,128] f32
    l_ref,  # VMEM scratch [G,128] f32
    acc_ref,  # VMEM scratch [G,D] f32
    *,
    block_t: int,
    n_blocks: int,
    scale: float,
):
    """Flash decode over an int8 KV cache. Dequantization never
    materialises: K's per-position scale multiplies the SCORE column it
    produced (scales commute with the q·k dot over D), and V's scale
    folds into the probability row before the p·v dot — two [G,Tb]
    multiplies per block instead of a [Tb,D] dequant."""
    b_i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b_i]
    block_start = j * block_t

    @pl.when(block_start < length)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # [G,D]
        k = k_ref[0, 0].astype(jnp.float32)  # [Tb,D] int8 codes
        # scales ride a trailing singleton lane dim: a [...,Tb] block
        # would put 1 in the sublane slot over Hkv>1, which Mosaic's
        # tiling rule rejects (the bug that made this kernel fail to
        # lower on real TPU for ANY batched int8-KV shape)
        ks = ks_ref[0, 0, :, 0].astype(jnp.float32)  # [Tb]
        vs = vs_ref[0, 0, :, 0].astype(jnp.float32)  # [Tb]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
            * ks[None, :]
        )  # [G,Tb] — k dequant applied as a per-column score scale
        idx = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < length, s, -jnp.inf)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [G,Tb]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # [Tb,D] int8 codes
        pv = jax.lax.dot_general(
            p * vs[None, :],  # v dequant folded into the probability row
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G,D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blocks - 1)
    def _finalise():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def pallas_decode_attention_int8(
    q: jnp.ndarray,  # [B,Hq,D]
    k_q: jnp.ndarray,  # [B,Hkv,T,D] int8
    k_s: jnp.ndarray,  # [B,Hkv,T] f32
    v_q: jnp.ndarray,  # [B,Hkv,T,D] int8
    v_s: jnp.ndarray,  # [B,Hkv,T] f32
    lengths: jnp.ndarray,  # [B] int32
    *,
    block_t: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash-decode attention over an int8-quantized KV cache — math-
    identical to running :func:`pallas_decode_attention` on the
    dequantized cache (scales commute with the dots)."""
    b, hq, d = q.shape
    _, hkv, t, _ = k_q.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)

    if interpret is None:
        interpret = not on_tpu()

    d_pad = (-d) % 128
    if d_pad:
        pad4 = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        q = jnp.pad(q.reshape(b, hkv, group, d), pad4)
        k_q = jnp.pad(k_q, pad4)
        v_q = jnp.pad(v_q, pad4)
        dp = d + d_pad
    else:
        q = q.reshape(b, hkv, group, d)
        dp = d

    bt = min(_pick_block_t(t, block_t), t)
    n_blocks = t // bt

    kernel = functools.partial(
        _decode_kernel_int8, block_t=bt, n_blocks=n_blocks, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        name="pallas_decode_attention_int8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, group, dp), lambda b_i, h, j, L: (b_i, h, 0, 0)),
                pl.BlockSpec((1, 1, bt, dp), lambda b_i, h, j, L: (b_i, h, j, 0)),
                # scales as [B,Hkv,T,1]: block (1,1,bt,1) puts bt in the
                # sublane slot (8-divisible) and the full singleton in
                # the lane slot — a rank-3 (1,1,bt) block leaves 1 over
                # Hkv in the sublane slot, which Mosaic rejects
                pl.BlockSpec((1, 1, bt, 1), lambda b_i, h, j, L: (b_i, h, j, 0)),
                pl.BlockSpec((1, 1, bt, dp), lambda b_i, h, j, L: (b_i, h, j, 0)),
                pl.BlockSpec((1, 1, bt, 1), lambda b_i, h, j, L: (b_i, h, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, dp), lambda b_i, h, j, L: (b_i, h, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dp), q.dtype),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        q,
        k_q,
        k_s.astype(jnp.float32)[..., None],
        v_q,
        v_s.astype(jnp.float32)[..., None],
    )

    if d_pad:
        out = out[..., :d]
    return out.reshape(b, hq, d)


def _prefill_kernel(
    offset_ref,  # SMEM [1] int32 (scalar-prefetched)
    q_ref,  # VMEM [1,1,block_q*G,D]
    k_ref,  # VMEM [1,1,block_k,D]
    v_ref,  # VMEM [1,1,block_k,D]
    o_ref,  # VMEM [1,1,block_q*G,D]
    m_ref,  # VMEM scratch [block_q*G,128] f32
    l_ref,  # VMEM scratch [block_q*G,128] f32
    acc_ref,  # VMEM scratch [block_q*G,D] f32
    *,
    block_q: int,
    block_k: int,
    group: int,
    scale: float,
):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # key block (innermost → sequential accumulation)
    offset = offset_ref[0]
    q_start = i * block_q  # first query *position* of this block
    # Causal frontier: the last cache position any row here attends is
    # offset + q_start + block_q - 1; k-blocks wholly beyond it are skipped.
    last_pos = offset + q_start + block_q - 1
    last_j = last_pos // block_k

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_k <= last_pos)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)  # [block_q*G, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [block_k, D]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [block_q*G, block_k]
        # Row r is query position q_start + r // G; causal mask by absolute
        # cache position (also masks the cache's unwritten suffix).
        qpos = (
            offset
            + q_start
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        )
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, -jnp.inf)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # [block_k, D]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == last_j)
    def _finalise():
        # Every row attends at least its own position, so l >= exp(0) > 0.
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def pallas_prefill_attention(
    q: jnp.ndarray,  # [B,S,Hq,D]
    k_cache: jnp.ndarray,  # [B,Hkv,T,D]
    v_cache: jnp.ndarray,  # [B,Hkv,T,D]
    offset: jnp.ndarray,  # scalar int32: cache position of q[:, 0]
    *,
    block_q: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Blockwise-causal flash prefill against the KV cache.

    Replaces the jnp prefill path's [S,T] score materialisation; the current
    chunk's K/V must already be written into the cache (exactly what
    ``models.transformer._attention_block`` does before attending).
    """
    b, s, hq, d = q.shape
    _, hkv, t, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)

    if interpret is None:
        interpret = not on_tpu()

    # [B,S,Hkv,G,D] → [B,Hkv,S·G,D]: the group folds into q rows so a block
    # is a dense [block_q·G, D] MXU operand.
    qr = q.reshape(b, s, hkv, group, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, s * group, d)

    d_pad = (-d) % 128
    if d_pad:
        pad = ((0, 0), (0, 0), (0, 0), (0, d_pad))
        qr = jnp.pad(qr, pad)
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    dp = d + d_pad

    bq = min(_pick_block_t(s, block_q), s)
    bk = min(_pick_block_t(t, block_k), t)
    n_qb, n_kb = s // bq, t // bk

    kernel = functools.partial(
        _prefill_kernel, block_q=bq, block_k=bk, group=group, scale=scale
    )
    rows = bq * group

    def kv_index(b_i, h, i, j, off):
        # Clamp past-the-frontier k-blocks to the last block this q-block
        # actually attends: Pallas elides the DMA when the block index
        # repeats, so the skipped iterations stream no K/V from HBM (their
        # compute is already gated off by pl.when in the kernel).
        last_j = jax.lax.div(off[0] + (i + 1) * bq - 1, bk)
        return (b_i, h, jnp.minimum(j, last_j), 0)

    out = pl.pallas_call(
        kernel,
        name="pallas_prefill_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_qb, n_kb),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, rows, dp), lambda b_i, h, i, j, O: (b_i, h, i, 0)
                ),
                pl.BlockSpec((1, 1, bk, dp), kv_index),
                pl.BlockSpec((1, 1, bk, dp), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, rows, dp), lambda b_i, h, i, j, O: (b_i, h, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, s * group, dp), q.dtype),
        interpret=interpret,
    )(jnp.atleast_1d(offset).astype(jnp.int32), qr, k_cache, v_cache)

    if d_pad:
        out = out[..., :d]
    # [B,Hkv,S·G,D] → [B,S,Hq,D]
    out = out.reshape(b, hkv, s, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hq, d)
