"""Ring attention: causal self-attention with the sequence sharded over ICI.

Long-context prefill support (task brief: "ring attention or all-to-all
sequence/context parallelism for long sequences"). Each device holds an
S/n_sp token shard of Q/K/V; K/V blocks rotate around the ring with
``jax.lax.ppermute`` while every device folds each visiting block into an
online-softmax accumulator — peak memory is O(S/n) per device and the
collective traffic rides neighbour-to-neighbour ICI links.

Causality is enforced at block granularity (a device only attends visiting
blocks that precede its own shard, with an exact triangular mask on the
diagonal block), so the result matches single-device causal attention
bit-for-bit up to f32 reduction order.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attend(
    q: jnp.ndarray,  # [B,Sq,Hkv,G,D] f32
    k: jnp.ndarray,  # [B,Sk,Hkv,D] f32
    v: jnp.ndarray,  # [B,Sk,Hkv,D] f32
    mask: jnp.ndarray,  # [Sq,Sk] bool (True = attend)
    m: jnp.ndarray,  # [B,Hkv,G,Sq,1]
    l: jnp.ndarray,  # [B,Hkv,G,Sq,1]
    acc: jnp.ndarray,  # [B,Hkv,G,Sq,D]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k) * scale  # [B,Hkv,G,Sq,Sk]
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # A fully-masked block keeps m at -inf; exp(-inf - -inf) would be NaN.
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_safe))
    p = jnp.exp(scores - m_safe)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bkgst,btkd->bkgsd", p, v)
    return m_new, l_new, acc_new


def ring_attention(
    q: jnp.ndarray,  # local shard [B, S_loc, Hq, D]
    k: jnp.ndarray,  # local shard [B, S_loc, Hkv, D]
    v: jnp.ndarray,  # local shard [B, S_loc, Hkv, D]
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Causal attention across the ``axis_name`` ring. Call inside shard_map."""
    n = axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, s_loc, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv

    qg = q.reshape(b, s_loc, hkv, group, d).astype(jnp.float32)
    m0 = jnp.full((b, hkv, group, s_loc, 1), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, group, s_loc, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((b, hkv, group, s_loc, d), dtype=jnp.float32)

    causal_diag = jnp.tril(jnp.ones((s_loc, s_loc), dtype=bool))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (me - step) % n  # ring position the visiting block came from
        mask = jnp.where(
            src == me,
            causal_diag,
            jnp.broadcast_to(src < me, (s_loc, s_loc)),
        )
        m, l, acc = _block_attend(
            qg, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), mask, m, l, acc
        )
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    # [B,Hkv,G,Sq,D] → [B,Sq,Hq,D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s_loc, hq, d)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh, axis_name: str = "sp"
) -> "functools.partial":
    """Wrap ``ring_attention`` in shard_map over ``mesh``: takes/returns
    sequence-sharded [B, S, H, D] global arrays."""
    seq_sharded = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name),
        mesh=mesh,
        in_specs=(seq_sharded, seq_sharded, seq_sharded),
        out_specs=seq_sharded,
        check_vma=False,
    )
    return fn
