"""The state-space mixer (Mamba-2 / SSD, arXiv:2405.21060) and its state.

A state-space layer keeps no cache of keys and values. For every row it
keeps a STATE of fixed size, whatever the row's length: ``s [H, P, N]``
float32 (``H`` heads of ``P`` values over ``N`` state values each) and
``conv``, the last ``K - 1`` inputs of the layer's causal convolution. A
model's state is the record ``{"s": [Ls, B, H, P, N], "conv": [Ls, B, K - 1,
C]}`` over its ``Ls`` state-space layers: allocated for a bucket of rows
(:func:`init_state`), read and written by a step or a chunk where it lies
(``run_blocks`` carries it through its layer scans), installed a row at a
time (:func:`install_state_row`), ``ModelConfig.state_bytes_per_row`` bytes
a row. It has no export yet: a snapshot for prefix sharing, preemption or a
migration bundle is refused by name (``UnsupportedMechanism``).

The mixer, for the normed input ``u [B, S, D]`` of a layer::

    [z | xBC | dt] = u W_in                                  # d_in | C | H, no bias
    c_t  = silu(b_conv + sum_k w_conv[k] * xBC_{t-(K-1)+k})  # depthwise, causal, the tail before the chunk
    [x | B | C] = c_t                                        # [H, P] | [G, N] | [G, N]
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t * A),  A = -exp(A_log)
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] * (x_t[h] outer B_t)
    y_t[h] = S_t[h] C_t + D[h] x_t[h]
    g_t  = flatten(y_t) * silu(z_t);  o_t = w_norm * g_t / sqrt(mean(g_t^2) + eps)
    out  = o W_out

in two forms of the one recurrence: a single token against the state (the
decode step: one pass over the ``s`` of the rows that are LIVE, where it
lies in the record, ``ops/pallas_ssm.py``; where that kernel does not fit,
:func:`ssm_step_impl`, XLA's pass over the whole bucket's), and a chunk of
tokens in SSD's chunked form (prefill: inside a block of
``ssm_chunk_size`` tokens masked matmuls with the cumulative decays,
between blocks the state), started from the state the previous chunk left.
A masked-out position moves nothing: ``dt = 0`` there, so ``a = 1`` and
nothing is added, and the convolution's tail is taken at the row's last
real position. Masks are prefixes: a row's real tokens come first.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_ssm import ssm_step_live, ssm_step_supported
from .config import ModelConfig
from .quantize import dense_dot, unpartitioned_kernels_enabled

State = Dict[str, Any]

# the stacked leaves of the state-space mixer, ``[state_layers, ...]`` each
SSM_LEAVES = (
    "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d",
    "ssm_norm", "ssm_out",
)


def init_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> State:
    """The zero state of ``batch`` rows: what a sequence starts from."""
    ls, k = cfg.state_layers, cfg.ssm_d_conv
    return {
        "s": jnp.zeros(
            (ls, batch, cfg.ssm_n_heads, cfg.ssm_d_head, cfg.ssm_d_state),
            dtype=jnp.float32,
        ),
        "conv": jnp.zeros((ls, batch, k - 1, cfg.ssm_conv_width), dtype=dtype),
    }


def state_bytes(state: State) -> int:
    return int(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)))


def install_state_row(state: State, r, row: State) -> State:
    """``state`` with row ``r`` (traced; outside the bucket: dropped) set
    to ``row``'s one row: a joiner starts from ITS state, not the slot's
    last owner's."""
    return jax.tree_util.tree_map(
        lambda a, u: a.at[:, r].set(u[:, 0].astype(a.dtype), mode="drop"),
        state, row,
    )


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _heads_of(cfg: ModelConfig, g):
    """``[..., G, N]`` of the groups as ``[..., H, N]`` of the heads."""
    rep = cfg.ssm_n_heads // cfg.ssm_n_groups
    return g if rep == 1 else jnp.repeat(g, rep, axis=-2)


def ssm_step_impl(cfg: ModelConfig, state: State, tokens: int) -> str:
    """Name of what a call of ``tokens`` tokens a row does with the record
    ``state``'s ``s`` -- the ONE rule: ``run_blocks`` branches on it at
    trace time and a session's ``/debug/state`` reports it.
    ``"pallas-live"``: the one-token step as one kernel a layer that reads
    and writes the LIVE rows' state where it lies in the record
    (``ops/pallas_ssm.py``), where the state fits it (float32, ``N`` in
    lane tiles, ``P`` in sublane tiles) and the trace is not a sharded
    engine's (the kernel has no partitioning rule). Else ``"xla-bucket"``:
    the layer's entry sliced out, :func:`_step` or :func:`_chunked` over
    every row of the bucket, and the entry written back (a chunk of
    tokens, small unaligned shapes)."""
    if (
        tokens == 1
        and unpartitioned_kernels_enabled()
        and ssm_step_supported(state["s"], cfg.ssm_n_groups)
    ):
        return "pallas-live"
    return "xla-bucket"


def _step(cfg, s0, x, bm, cm, dt, a_neg, d_skip):
    """One token: ``s0 [B,H,P,N]``, ``x [B,H,P]``, ``bm``/``cm [B,G,N]``,
    ``dt [B,H]`` (all float32). Both results come off ``s0``, so the state
    is read once: ``S_t C = a (S_{t-1} C) + dt x (B . C)``."""
    bh, ch = _heads_of(cfg, bm), _heads_of(cfg, cm)
    a = jnp.exp(dt * a_neg)  # [B,H]
    dx = dt[..., None] * x  # [B,H,P]
    y = (
        a[..., None] * jnp.einsum("bhpn,bhn->bhp", s0, ch)
        + jnp.sum(bh * ch, axis=-1)[..., None] * dx
        + d_skip[:, None] * x
    )
    s1 = a[..., None, None] * s0 + dx[..., None] * bh[:, :, None, :]
    return y, s1


def _chunked(cfg, s0, x, bm, cm, dt, a_neg, d_skip):
    """A chunk of tokens in SSD's chunked form: ``x [B,S,H,P]``, ``bm`` /
    ``cm [B,S,G,N]``, ``dt [B,S,H]`` (float32), from the state ``s0``.
    Blocks of ``ssm_chunk_size`` tokens; a scan over the blocks carries the
    state. Returns ``(y [B,S,H,P], s [B,H,P,N])``."""
    b, s, h, p = x.shape
    g, n = bm.shape[-2:]
    q = min(cfg.ssm_chunk_size, s)
    pad = (-s) % q
    if pad:  # dt = 0 there: nothing moves
        x, bm, cm, dt = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, bm, cm, dt)
        )
    nc = (s + pad) // q
    k = h // g  # heads a group

    def blocks(t):  # [B, nc*q, ...] -> [nc, B, q, ...]
        return jnp.moveaxis(t.reshape(b, nc, q, *t.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((q, q), dtype=bool))

    def block(s_in, xs):
        xq, bq, cq, dtq = xs  # [B,q,H,P] [B,q,G,N] [B,q,G,N] [B,q,H]
        cum = jnp.cumsum(dtq * a_neg, axis=1)  # [B,q,H], <= 0, falling
        cum_h = jnp.moveaxis(cum, 1, 2)  # [B,H,q]
        seg = cum_h[..., :, None] - cum_h[..., None, :]  # [B,H,i,j]
        decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
        scores = jnp.einsum("bign,bjgn->bgij", cq, bq)  # [B,G,q,q]
        m = (
            jnp.repeat(scores, k, axis=1) if k > 1 and g > 1 else scores
        )  # [B,G|H,q,q]; one group broadcasts over the heads
        m = m * decay * jnp.moveaxis(dtq, 1, 2)[..., None, :]  # [B,H,i,j]
        y = jnp.einsum("bhij,bjhp->bihp", m, xq)
        s_g = s_in.reshape(b, g, k, p, n)
        y_init = jnp.einsum("bgkpn,bign->bigkp", s_g, cq).reshape(b, q, h, p)
        y = y + jnp.exp(cum)[..., None] * y_init + d_skip[:, None] * xq
        to_end = jnp.exp(cum[:, -1:, :] - cum) * dtq  # [B,q,H]
        wx = (to_end[..., None] * xq).reshape(b, q, g, k, p)
        s_add = jnp.einsum("bjgkp,bjgn->bgkpn", wx, bq).reshape(b, h, p, n)
        s_out = jnp.exp(cum[:, -1, :])[..., None, None] * s_in + s_add
        return s_out, y

    s_out, ys = jax.lax.scan(block, s0, tuple(blocks(t) for t in (x, bm, cm, dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * q, h, p)
    return y[:, :s], s_out


def ssm_mixer(
    cfg: ModelConfig,
    u: jnp.ndarray,  # [B,S,D], normed
    layer: Dict[str, Any],  # one layer's SSM_LEAVES
    st: State,  # this layer's {"s": [B,H,P,N], "conv": [B,K-1,C]}
    token_mask: Optional[jnp.ndarray] = None,  # [B,S] bool, a prefix a row
    in_record: Optional[Tuple[Any, jnp.ndarray, Any]] = None,
) -> Tuple[jnp.ndarray, State]:
    """The mixer's output ``[B,S,D]`` and the layer's state after the
    tokens: the step form for one token, the chunked form for more.

    ``in_record`` (one token, :func:`ssm_step_impl` ``"pallas-live"``):
    ``(at, rows, n_live)``. ``st["s"]`` is then the RECORD's ``[Ls, B, H,
    P, N]``, of which the step reads and writes entry ``at`` of the rows
    ``rows[:n_live]`` (``token_mask``'s, compacted) and nothing else, and
    the record comes back as the result's ``"s"``; ``conv`` is the
    layer's either way."""
    b, s, _ = u.shape
    h, p, n, g = cfg.ssm_n_heads, cfg.ssm_d_head, cfg.ssm_d_state, cfg.ssm_n_groups
    d_in, c_w, k = cfg.ssm_d_inner, cfg.ssm_conv_width, cfg.ssm_d_conv
    f32 = jnp.float32
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = dense_dot(u, layer["ssm_in"])
        z, xbc, dt = (
            zxbcdt[..., :d_in], zxbcdt[..., d_in : d_in + c_w], zxbcdt[..., d_in + c_w :]
        )
    with jax.named_scope("ssm.conv"):
        padded = jnp.concatenate([st["conv"].astype(xbc.dtype), xbc], axis=1)
        w = layer["ssm_conv_w"].astype(f32)  # [K, C]
        acc = layer["ssm_conv_b"].astype(f32) + sum(
            w[i] * padded[:, i : i + s].astype(f32) for i in range(k)
        )
        c = jax.nn.silu(acc)  # [B,S,C] float32
        # the tail at the row's last real position: the K - 1 inputs that
        # end there (fewer real tokens than that: the old tail's end too)
        n_real = (
            jnp.full((b,), s, dtype=jnp.int32)
            if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32)
        )
        at = n_real[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    with jax.named_scope("ssm.update"):
        x = c[..., :d_in].reshape(b, s, h, p)
        bm = c[..., d_in : d_in + g * n].reshape(b, s, g, n)
        cm = c[..., d_in + g * n :].reshape(b, s, g, n)
        dt = _softplus(dt.astype(f32) + layer["ssm_dt_bias"].astype(f32))
        if token_mask is not None:
            dt = jnp.where(token_mask[..., None], dt, 0.0)
        a_neg = -jnp.exp(layer["ssm_a_log"].astype(f32))
        d_skip = layer["ssm_d"].astype(f32)
        if in_record is not None:
            at, rows, n_live = in_record
            y, s_new = ssm_step_live(
                st["s"], at, rows, n_live, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                a_neg, d_skip,
            )
            y = y[:, None]
        elif s == 1:
            y, s_new = _step(
                cfg, st["s"], x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0], a_neg, d_skip
            )
            y = y[:, None]
        else:
            y, s_new = _chunked(cfg, st["s"], x, bm, cm, dt, a_neg, d_skip)
    with jax.named_scope("ssm.gate_norm"):
        gated = y.reshape(b, s, d_in) * jax.nn.silu(z.astype(f32))
        o = gated * jax.lax.rsqrt(
            jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps
        )
        o = (o * layer["ssm_norm"].astype(f32)).astype(u.dtype)
    with jax.named_scope("ssm.out_proj"):
        out = dense_dot(o, layer["ssm_out"])
    return out, {"s": s_new, "conv": tail.astype(st["conv"].dtype)}
