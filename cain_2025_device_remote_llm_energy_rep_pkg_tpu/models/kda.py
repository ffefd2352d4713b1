"""Kimi Delta Attention (KDA, arXiv:2510.26692): a linear-attention mixer
whose state is a delta rule with a per-channel decay.

Like a Mamba-2 layer (``models/ssm.py``) a KDA layer keeps no cache of keys
and values: for every row it keeps a STATE of fixed size, ``s [H, K, V]``
float32 (``H`` heads, ``K`` = ``V`` = ``kda_d_head``) and ``conv``, the last
``kda_d_conv - 1`` inputs of its causal convolution over ``q | k | v``. The
record is the one ``models/ssm.py`` allocates and installs
(``init_state``, ``install_state_row``), shaped by ``ModelConfig.state_dims``
and ``state_conv``; ``run_blocks`` carries it through its layer scans.

The mixer, for the normed input ``u [B, S, D]`` of a layer::

    [qkv | b | fa | ga] = u W_in                         # 3 H K | H | K | K
    f = fa W_fb;  gate = ga W_gb                          # [H K] each
    q, k, v = silu(causal conv(qkv))                      # depthwise, no bias
    q, k = q / |q|, k / |k| (a head's K values);  q = q / sqrt(K)
    beta = sigmoid(b);  g = L sigmoid(exp(A_log) (f + dt_bias))      # L = kda_lower_bound
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y = (norm(o_t) * w_norm * sigmoid(gate)) W_out        # norm over a head's V values

in two forms of the one recurrence. The one-token step (decode) reads the
state once: ``S' = D S``, ``u = beta (v - S'^T k)``, ``S = S' + k u^T``, ``o =
S^T q``. A chunk of tokens (prefill) runs in blocks of ``kda_chunk(cfg)``
tokens in the WY / UT form of the gated delta rule: inside a block, with
``G`` the cumulative log-decay, ``A[t, s] = beta_t sum_c k_t k_s exp(G_t -
G_s)`` (s < t) and ``(I + A) U = beta (V - (k exp G) S_0)`` gives every
token's ``u`` at once, ``o = (q exp G) S_0 + [q_t . k_s exp(G_t - G_s)]_{s <= t}
U`` and the state after the block ``exp(G_C) S_0 + (k exp(G_C - G))^T U``;
between blocks the state. The pairwise decays factor about the block's
middle, so a block is short enough that ``exp`` of half its span of
log-decay stays inside float32 (``kda_chunk``). A masked-out position
moves nothing (``g = 0``, ``beta = 0``), and the convolution's tail is taken
at the row's last real position; masks are prefixes.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_kda import kda_step_live, kda_step_supported
from .config import ModelConfig
from .quantize import dense_dot, unpartitioned_kernels_enabled

State = Dict[str, Any]

# the stacked leaves of the KDA mixer, ``[state_layers, ...]`` each
KDA_LEAVES = (
    "kda_in", "kda_conv_w", "kda_fb", "kda_gb", "kda_a_log", "kda_dt_bias",
    "kda_norm", "kda_out",
)

# log-decay the factored pairwise decays of a block may span about its
# middle: exp(+-40) lies far inside float32 (and its normal range)
_HALF_SPAN = 40.0
_HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def kda_chunk(cfg: ModelConfig) -> int:
    """Tokens a block of the chunked form: the largest power of two (at
    most 64) over half of which the steepest decay, ``kda_lower_bound`` a
    token, stays within ``_HALF_SPAN``."""
    c = 1
    while c < 64 and abs(cfg.kda_lower_bound) * c <= _HALF_SPAN:
        c *= 2
    return c


def kda_step_impl(cfg: ModelConfig, state: State, tokens: int) -> str:
    """Name of what a call of ``tokens`` tokens a row does with the record
    ``state``'s ``s`` -- the ONE rule ``run_blocks`` branches on and a
    session's ``/debug/state`` reports. ``"pallas-live"``: the one-token
    step as one kernel a layer that reads and writes the LIVE rows' state
    where it lies in the record (``ops/pallas_kda.py``), where the state
    fits it (float32, values in lane tiles, keys in sublane tiles) and the
    trace is not a sharded engine's (the kernel has no partitioning rule).
    Else ``"xla-bucket"``: the layer's entry sliced out, :func:`_step` or
    :func:`_chunked` over every row of the bucket, and the entry written
    back (a chunk of tokens, small unaligned shapes)."""
    del cfg
    if (
        tokens == 1
        and unpartitioned_kernels_enabled()
        and kda_step_supported(state["s"])
    ):
        return "pallas-live"
    return "xla-bucket"


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def _step(s0, q, k, v, g, beta):
    """One token: ``s0 [B,H,K,V]``, ``q``/``k``/``g [B,H,K]``, ``v
    [B,H,V]``, ``beta [B,H]`` (float32). Elementwise products and sums:
    the state is read once and every value stays float32."""
    sd = jnp.exp(g)[..., None] * s0
    u = beta[..., None] * (v - jnp.sum(sd * k[..., None], axis=-2))
    s1 = sd + k[..., None] * u[..., None, :]
    return jnp.sum(s1 * q[..., None], axis=-2), s1


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a [..., C, C]`` (C a
    power of two): ``prod_j (I + (-a)^(2^j))``, the series ``sum_i (-a)^i``
    that ends at ``(-a)^C = 0``."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    x, inv = -a, eye - a
    for _ in range(int(math.log2(c)) - 1):
        x = jnp.einsum("...ij,...jk->...ik", x, x, precision=_HIGHEST)
        inv = jnp.einsum("...ij,...jk->...ik", inv, eye + x, precision=_HIGHEST)
    return inv


def _chunked(cfg, s0, q, k, v, g, beta):
    """A chunk of tokens: ``q``/``k``/``g [B,S,H,K]``, ``v [B,S,H,V]``,
    ``beta [B,S,H]`` (float32), from the state ``s0 [B,H,K,V]``. Returns
    ``(o [B,S,H,V], s [B,H,K,V])``."""
    b, s, h, _ = q.shape
    c = min(kda_chunk(cfg), 1 << max(0, (s - 1).bit_length()))
    pad = (-s) % c
    if pad:  # g = 0, beta = 0 there: nothing moves
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta)
        )
    nc = (s + pad) // c

    def blocks(t):  # [B, nc*c, H, ...] -> [nc, B, H, c, ...]
        t = t.reshape(b, nc, c, h, *t.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)

    strict = jnp.tril(jnp.ones((c, c), dtype=bool), -1)
    upto = jnp.tril(jnp.ones((c, c), dtype=bool))

    def ein(spec, x, y):
        return jnp.einsum(spec, x, y, precision=_HIGHEST)

    def block(s_in, xs):
        qc, kc, vc, gc, bc = xs  # [B,H,c,K] x2, [B,H,c,V], [B,H,c,K], [B,H,c]
        cum = jnp.cumsum(gc, axis=2)  # <= 0, falling
        mid = cum[:, :, c // 2 : c // 2 + 1]
        k_up, k_down = kc * jnp.exp(cum - mid), kc * jnp.exp(mid - cum)
        a = jnp.where(strict, bc[..., None] * ein("bhtc,bhsc->bhts", k_up, k_down), 0.0)
        w = bc[..., None] * (vc - ein("bhtc,bhcv->bhtv", kc * jnp.exp(cum), s_in))
        u = ein("bhts,bhsv->bhtv", _unit_lower_inverse(a), w)
        qk = jnp.where(upto, ein("bhtc,bhsc->bhts", qc * jnp.exp(cum - mid), k_down), 0.0)
        o = ein("bhtc,bhcv->bhtv", qc * jnp.exp(cum), s_in) + ein("bhts,bhsv->bhtv", qk, u)
        last = cum[:, :, -1:]
        s_out = jnp.exp(last)[:, :, 0, :, None] * s_in + ein(
            "bhsc,bhsv->bhcv", kc * jnp.exp(last - cum), u
        )
        return s_out, o

    s_out, os = jax.lax.scan(block, s0, tuple(blocks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(os, 0, 1), 3, 2).reshape(b, nc * c, h, -1)
    return o[:, :s], s_out


def kda_mixer(
    cfg: ModelConfig,
    u: jnp.ndarray,  # [B,S,D], normed
    layer: Dict[str, Any],  # one layer's KDA_LEAVES
    st: State,  # this layer's {"s": [B,H,K,V], "conv": [B,Kc-1,3 H K]}
    token_mask: Optional[jnp.ndarray] = None,  # [B,S] bool, a prefix a row
    in_record: Optional[Tuple[Any, jnp.ndarray, Any]] = None,
) -> Tuple[jnp.ndarray, State]:
    """The mixer's output ``[B,S,D]`` and the layer's state after the
    tokens: the step form for one token, the chunked form for more.

    ``in_record`` (one token, :func:`kda_step_impl` ``"pallas-live"``):
    ``(at, rows, n_live)``. ``st["s"]`` is then the RECORD's ``[Ls, B, H,
    K, V]``, of which the step reads and writes entry ``at`` of the rows
    ``rows[:n_live]`` and nothing else, and the record comes back as the
    result's ``"s"``; ``conv`` is the layer's either way."""
    b, s, _ = u.shape
    h, dk, taps = cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_conv
    di = cfg.kda_d_inner
    f32 = jnp.float32
    with jax.named_scope("kda.in_proj"):
        proj = dense_dot(u, layer["kda_in"])
        qkv, b_logit = proj[..., : 3 * di], proj[..., 3 * di : 3 * di + h]
        fa, ga = proj[..., 3 * di + h : 3 * di + h + dk], proj[..., 3 * di + h + dk :]
        f = dense_dot(fa, layer["kda_fb"])
        gate = dense_dot(ga, layer["kda_gb"])
    with jax.named_scope("kda.conv"):
        padded = jnp.concatenate([st["conv"].astype(qkv.dtype), qkv], axis=1)
        w = layer["kda_conv_w"].astype(f32)  # [Kc, 3 H K]
        c = jax.nn.silu(
            sum(w[i] * padded[:, i : i + s].astype(f32) for i in range(taps))
        )
        n_real = (
            jnp.full((b,), s, dtype=jnp.int32)
            if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32)
        )
        at = n_real[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    with jax.named_scope("kda.update"):
        q = _l2(c[..., :di].reshape(b, s, h, dk)) / math.sqrt(dk)
        k = _l2(c[..., di : 2 * di].reshape(b, s, h, dk))
        v = c[..., 2 * di :].reshape(b, s, h, dk)
        beta = jax.nn.sigmoid(b_logit.astype(f32))
        a = jnp.exp(layer["kda_a_log"].astype(f32))[:, None]  # [H, 1]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            a * (f.astype(f32) + layer["kda_dt_bias"].astype(f32)).reshape(b, s, h, dk)
        )
        if token_mask is not None:
            g = jnp.where(token_mask[..., None, None], g, 0.0)
            beta = jnp.where(token_mask[..., None], beta, 0.0)
        if in_record is not None:
            at, rows, n_live = in_record
            o, s_new = kda_step_live(
                st["s"], at, rows, n_live, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
            )
            o = o[:, None]
        elif s == 1:
            o, s_new = _step(st["s"], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            o = o[:, None]
        else:
            o, s_new = _chunked(cfg, st["s"], q, k, v, g, beta)
    with jax.named_scope("kda.gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = o * layer["kda_norm"].astype(f32) * jax.nn.sigmoid(
            gate.astype(f32).reshape(b, s, h, dk)
        )
        o = o.reshape(b, s, di).astype(u.dtype)
    with jax.named_scope("kda.out_proj"):
        out = dense_dot(o, layer["kda_out"])
    return out, {"s": s_new, "conv": tail.astype(st["conv"].dtype)}
