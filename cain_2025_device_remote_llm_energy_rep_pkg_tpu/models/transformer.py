"""Shared decoder-only transformer over a plain-pytree parameter dict.

TPU-first design choices:
- Layer parameters are *stacked* along a leading [L, ...] axis and the block
  loop is a ``lax.scan`` over layers — one traced block regardless of depth,
  so a 32-layer model compiles as fast as a 2-layer one and XLA pipelines
  HBM weight streaming.
- bfloat16 weights/activations with float32 softmax/norm accumulation (MXU
  native dtype).
- One unified ``forward`` serves prefill (S tokens, offset 0) and decode
  (S=1 at offset t): current K/V are written into the fixed-size cache with
  ``dynamic_update_slice`` and attention masks by absolute position
  ``kpos <= qpos``, so no separate length bookkeeping is needed.

The reference has no model code at all (generation is delegated to the
external Ollama server, experiment/RunnerConfig.py:128-131); this module is
the TPU-native replacement mandated by BASELINE.json's north star.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.pallas_moe import grouped_expert_ffn, grouped_ffn_supported
from ..ops.pallas_paged_side import paged_side_parts, paged_side_supported
from ..ops.pallas_ssm import live_rows
from ..ops.rope import apply_rope, rope_angles, rope_score_scale
from .config import (
    FFN_DENSE_THEN_EXPERTS,
    FFN_EXPERTS,
    FFN_EXPERTS_BESIDE_DENSE,
    MIXER_ATTENTION,
    MIXER_KDA,
    MIXER_SSM,
    STATE_MIXERS,
    ModelConfig,
)
from .kda import KDA_LEAVES, kda_mixer, kda_step_impl
from .quantize import (
    dense_dot,
    dequant_cache,
    embed_lookup,
    is_quantized,
    is_quantized_cache,
    maybe_dequant,
    quantize_kv_vector,
    unpartitioned_kernels_enabled,
)
from .ssm import SSM_LEAVES, init_state, ssm_mixer, ssm_step_impl

Params = Dict[str, Any]

# Param leaves WITHOUT the leading stacked-layer [L, …] axis. Everything not
# named here is scanned as a per-layer block (forward) and stage-sharded by
# pipeline parallelism (parallel/pp.py) — keep the two views in sync by
# defining the set exactly once, here.
NON_LAYER_LEAVES = ("embed", "final_norm", "lm_head")

# Signature: (q[B,Hq,D], k_cache[B,Hkv,T,D], v_cache[B,Hkv,T,D], lengths[B]) -> [B,Hq,D]
DecodeAttentionFn = Callable[
    [jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray
]


STACKED_PAGED_KEYS = frozenset(
    {
        "pool",
        "table",
        "layer",
        "side",
        "side_layer",
        "write_pos",
        "prompt_lens",
        "owners",
        "live",
    }
)


def is_paged_cache(leaf: Any) -> bool:
    """A paged KV-cache leaf: ``{"pool": [P,Hkv,page,D], "table":
    [B,Jmax]}`` (engine/paged_kv.py) — pages of a shared pool addressed
    through a per-request block table. The STACKED-HYBRID variant (the
    fast batched-decode path) additionally carries: the pool (READ-ONLY
    during decode — prefill pages only; [L,P,Hkv,page,Dp] at the engine
    boundary, a per-layer [P,Hkv,page,Dp] xs slice inside the layer
    scan), a contiguous ``side`` cache [B,Hkv,Tgen,D] per layer holding
    the tokens generated this call, and ``write_pos``/``prompt_lens``
    [B] row vectors, and on the K leaf, where no page has two readers,
    ``owners``: the table's inverse (ops/pallas_paged_attention.py
    ``pool_page_owners``), by which the XLA parts path reads the pool
    in place, and with the held pages' list the held-page kernel. An
    optional ``layer`` index marks a whole stacked pool addressed
    inside a kernel's DMA offset (the held-page kernel's form; the
    page-table kernel's non-default variant, kept parity-tested). The
    SCRATCH variant ``{"pool", "table", "scratch"}`` is the kernel-less
    speculative VERIFY form: the pool is READ-ONLY for the forward, the
    block's candidate K/V land in the small per-layer ``scratch`` [B,Hkv,S,D]
    (or int8 ``{"q","s"}``) instead of being written through the page
    table — the caller commits only what survives acceptance."""
    if not isinstance(leaf, dict):
        return False
    keys = set(leaf)
    return (
        keys == {"pool", "table"}
        or keys == {"pool", "table", "scratch"}
        or ({"pool", "table", "side"} <= keys <= STACKED_PAGED_KEYS)
    )


def is_carry_cache(leaf: Any) -> bool:
    """A carry-resident KV-cache leaf: ``{"all": [L,B,Hkv,T,D], "layer":
    l}`` — the WHOLE stacked cache rides the decode loop's carry and each
    layer writes only its token's row in place at ``[layer, rows, :,
    offset]``. Used by batched single-token decode: the alternative
    (caches as layer-scan xs AND ys) makes XLA write back the full
    per-layer cache every layer every step — 1.4 GB/step of pure copy
    at 128 rows for a 64 KB actual update (from shapes; its time on the
    chip: not measured), the dominant batch-scaling cost.
    The per-layer READ stays (attention consumes the whole slice); only
    the write-back copies go. ``all`` is either a plain array or an
    int8-KV ``{"q": [L,B,Hkv,T,D], "s": [L,B,Hkv,T]}`` dict — the
    quantized batched path pays the same per-layer write-back tax as the
    plain one and gets the same cure."""
    return isinstance(leaf, dict) and set(leaf) == {"all", "layer"}


def is_state_cache(leaf: Any) -> bool:
    """The K-side cache of a model with state-space layers: ``{"kv": <any
    of the K cache leaves above, over the ATTENTION layers>, "ssm": <the
    recurrent state over the state-space layers, models/ssm.py>}``. The
    record enters and leaves :func:`forward` / :func:`run_blocks` in the K
    cache's place, so that every path that threads a cache (a prefill
    chunk, a decode loop's carry, a session's slice step) threads the state
    with it; every leaf of both parts has the rows on axis 1."""
    return isinstance(leaf, dict) and set(leaf) == {"kv", "ssm"}


def _gather_paged(leaf, dtype=jnp.float32) -> jnp.ndarray:
    """Materialise a paged cache as contiguous [B,Hkv,T,D] — the jnp
    fallback path only; the Pallas kernels read through the table.
    Stacked-hybrid leafs are rejected: their pool holds only the prompt
    (generated tokens live in the side caches) and only the
    parts-kernel + merge path composes the two — a gather here would
    silently drop every generated token from attention."""
    if "side" in leaf or "layer" in leaf:
        raise ValueError(
            "stacked paged caches have no gather fallback (the pool "
            "holds only the prompt; the parts-kernel path merges the "
            "side cache) - the engine gates stacked mode on kernel "
            "presence, so reaching this is a wiring bug"
        )
    pool, table = leaf["pool"], leaf["table"]
    b, jmax = table.shape
    if isinstance(pool, dict):  # int8 pages: dequant the gathered pages
        _, hkv, page, dpool = pool["q"].shape
        gathered = pool["q"][table].astype(jnp.float32) * (
            pool["s"][table].astype(jnp.float32)[..., None]
        )  # [B, Jmax, Hkv, page, D]
    else:
        _, hkv, page, dpool = pool.shape
        gathered = pool[table]
    return (
        gathered.transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, jmax * page, dpool)
        .astype(dtype)
    )

# Signature: (q[B,S,Hq,D], k_cache[B,Hkv,T,D], v_cache[B,Hkv,T,D], offset) -> [B,S,Hq,D]
PrefillAttentionFn = Callable[
    [jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray
]


def init_params(
    cfg: ModelConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    post: Optional[Callable[[str, jnp.ndarray], Any]] = None,
) -> Params:
    """Random-init weights directly on the default device (HBM).

    ``post(name, leaf)`` (default identity) is applied to each leaf as it
    is created — the quantized engine streams init+quantize per tensor so
    the device never holds the full-precision model (llama3.1:8b bf16
    alone fills a 16 GB chip)."""
    keys = jax.random.split(key, 12)
    d, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    kind = cfg.ffn_kind
    # a dense prefix before expert layers: the dense FFN's leaves are as
    # long as the prefix, the expert layer's as long as the rest
    l_dense = cfg.n_dense_layers or l
    l_moe = cfg.n_expert_layers
    # a stack of several mixers: a mixer's leaves are as long as the layers
    # of its kind (every layer attention: the whole stack)
    l_attn = cfg.attention_layers
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if post is None:
        post = lambda _name, leaf: leaf  # noqa: E731

    def mat(k, shape, fan_in):
        return (
            jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)
        ).astype(dtype)

    # MoE MLPs carry a leading expert axis [L, E, D, F]; dense is [L, D, F].
    e = (cfg.n_experts,) if cfg.n_experts else ()
    n_blk = cfg.blocks_per_layer

    def ones_or_zeros(shape):
        return (
            jnp.ones(shape, dtype=dtype)
            if not cfg.gemma_norm
            else jnp.zeros(shape, dtype=dtype)
        )

    # The 12 keys and the key of every leaf a dense model has stay as they
    # are (another split count would change every dense model's weights).
    # A layer of several attention blocks keeps ONE LEAF A BLOCK, named
    # ``<leaf>_<j>`` and shaped like a dense model's, so that the layer
    # scan slices each block's weights exactly as it slices a dense
    # layer's (a block axis inside one leaf made the compiled step copy a
    # layer's slice before every matmul); block j draws from
    # ``fold_in(keys[i], j)``.
    def bkey(i, j):
        return keys[i] if n_blk == 1 else jax.random.fold_in(keys[i], j)

    params: Params = {}

    def put(name, leaf):
        params[name] = post(name, leaf)

    put(
        "embed",
        (
            jax.random.normal(keys[0], (cfg.vocab_size, d), dtype=jnp.float32)
            * cfg.init_embed_std
        ).astype(dtype),
    )
    for j in range(n_blk):
        sfx = f"_{j}" if n_blk > 1 else ""
        put("attn_norm" + sfx, ones_or_zeros((l, d)))
        if cfg.latent:
            rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
            up = cfg.qk_nope_head_dim + cfg.v_head_dim
            hv = hq * cfg.v_head_dim
            put("w_qa" + sfx, mat(bkey(1, j), (l_attn, d, rq), d))
            put("q_a_norm" + sfx, ones_or_zeros((l_attn, rq)))
            put("w_qb" + sfx, mat(bkey(10, j), (l_attn, rq, hq * dh), rq))
            put("w_kva" + sfx, mat(bkey(2, j), (l_attn, d, cfg.latent_row_width), d))
            put("kv_a_norm" + sfx, ones_or_zeros((l_attn, rkv)))
            put("w_kvb" + sfx, mat(bkey(3, j), (l_attn, rkv, hq * up), rkv))
            put("wo" + sfx, mat(bkey(4, j), (l_attn, hv, d), hv))
            if cfg.index_topk:
                # the lightning indexer, from ik[i] = fold_in(keys[1], 16 + i)
                # (keys[1] itself is w_qa's): its queries off the query
                # bottleneck, its one key and its head weights off the input
                hi, di = cfg.index_n_heads, cfg.index_head_dim
                ik = [jax.random.fold_in(bkey(1, j), 16 + i) for i in range(3)]
                put("idx_wq" + sfx, mat(ik[0], (l_attn, rq, hi * di), rq))
                put("idx_wk" + sfx, mat(ik[1], (l_attn, d, di), d))
                put("idx_ww" + sfx, mat(ik[2], (l_attn, d, hi), d))
        else:
            put("wq" + sfx, mat(bkey(1, j), (l_attn, d, hq * dh), d))
            put("wk" + sfx, mat(bkey(2, j), (l_attn, d, hkv * dh), d))
            put("wv" + sfx, mat(bkey(3, j), (l_attn, d, hkv * dh), d))
            put("wo" + sfx, mat(bkey(4, j), (l_attn, hq * dh, d), hq * dh))
        put("mlp_norm" + sfx, ones_or_zeros((l, d)))
        if kind != FFN_EXPERTS:
            put("w_gate" + sfx, mat(bkey(5, j), (l_dense, d, f), d))
            put("w_up" + sfx, mat(bkey(6, j), (l_dense, d, f), d))
            put("w_down" + sfx, mat(bkey(7, j), (l_dense, f, d), f))
        if cfg.residual_streams > 1:
            # one map a sublayer, float32 and never quantized. Stand-in
            # values (the published ones start near the plain residual;
            # these keep the data-dependent half of the map well above
            # rounding, so that a comparison sees a fault in it): phi of
            # unit-scale outputs, the three gains 1, read and write biases
            # normal * 0.5, mixing bias 3 I + normal * 0.5.
            n = cfg.residual_streams
            outs = cfg.hc_map_outputs
            eye = jnp.concatenate(
                [jnp.zeros((2 * n,)), 3.0 * jnp.eye(n).reshape(-1)]
            )
            for which, sub in enumerate(("attn", "mlp")):
                hk = jax.random.fold_in(bkey(11, j), 6 + which)
                put(
                    f"hc_{sub}_phi" + sfx,
                    jax.random.normal(
                        jax.random.fold_in(hk, 0), (l, n * d, outs), dtype=jnp.float32
                    )
                    / math.sqrt(n * d),
                )
                put(f"hc_{sub}_alpha" + sfx, jnp.ones((l, 3), dtype=jnp.float32))
                put(
                    f"hc_{sub}_bias" + sfx,
                    jax.random.normal(
                        jax.random.fold_in(hk, 1), (l, outs), dtype=jnp.float32
                    )
                    * 0.5
                    + eye,
                )
    if cfg.state_mixer == MIXER_KDA:
        # the KDA mixers (models/kda.py), from kk[i] = fold_in(keys[10], 16
        # + i) (keys[10] itself is a latent block's w_qb). The projections
        # by fan-in, made (and handed to ``post``) a layer at a time; the
        # gates' second stages by their rank. STAND-IN values for the rest
        # (Mamba-2's recipe for a decay, as Kimi Linear's reference code
        # takes it): ``A_log = log uniform[1, 16]`` a head, ``dt_bias`` the
        # inverse softplus of a step log-uniform in [1e-3, 1e-1] a channel,
        # the convolution normal / sqrt(its width), the norm's gain 1.
        lk, h_k, dk = cfg.state_layers, cfg.kda_n_heads, cfg.kda_d_head
        taps, channels = cfg.state_conv
        kk = [jax.random.fold_in(keys[10], 16 + i) for i in range(7)]
        for i, (name, shape, fan_in) in enumerate((
            ("kda_in", (d, cfg.kda_in_width), d),
            ("kda_out", (cfg.kda_d_inner, d), cfg.kda_d_inner),
        )):
            params[name] = jax.lax.map(
                lambda li, name=name, shape=shape, fan_in=fan_in, pk=kk[i]: post(
                    name, mat(jax.random.fold_in(pk, li), shape, fan_in)
                ),
                jnp.arange(lk),
            )
        put("kda_fb", mat(kk[2], (lk, dk, cfg.kda_d_inner), dk))
        put("kda_gb", mat(kk[3], (lk, dk, cfg.kda_d_inner), dk))
        put("kda_conv_w", mat(kk[4], (lk, taps, channels), taps))
        put(
            "kda_a_log",
            jnp.log(jax.random.uniform(kk[5], (lk, h_k), jnp.float32, 1.0, 16.0)),
        )
        dt0 = jnp.exp(
            jax.random.uniform(
                kk[6], (lk, cfg.kda_d_inner), jnp.float32, math.log(1e-3), math.log(1e-1)
            )
        )
        put("kda_dt_bias", dt0 + jnp.log(-jnp.expm1(-dt0)))
        put("kda_norm", jnp.ones((lk, dk), dtype=dtype))
    if cfg.state_mixer == MIXER_SSM:
        # the state-space mixers, from the eleventh key (only a latent
        # block's ``w_qb`` draws from it, and no such model has them). The
        # projections by fan-in; STAND-IN values for the rest (Mamba-2's
        # reference initialisation, arXiv:2405.21060): ``A_log = log
        # uniform[1, 16]``, ``dt_bias`` the inverse softplus of a step
        # log-uniform in [1e-3, 1e-1], ``D`` 1, the convolution normal /
        # sqrt(its width), its bias normal * 0.02, the gated norm's gain 1.
        ls, h_s = cfg.state_layers, cfg.ssm_n_heads
        c_w, k_c = cfg.ssm_conv_width, cfg.ssm_d_conv
        sk = [jax.random.fold_in(keys[10], i) for i in range(6)]
        # the two projections made (and handed to ``post``) a layer at a
        # time, as the experts below are: layer i from fold_in(sk, i)
        for i, (name, shape, fan_in) in enumerate((
            ("ssm_in", (d, cfg.ssm_in_width), d),
            ("ssm_out", (cfg.ssm_d_inner, d), cfg.ssm_d_inner),
        )):
            params[name] = jax.lax.map(
                lambda li, name=name, shape=shape, fan_in=fan_in, pk=sk[i]: post(
                    name, mat(jax.random.fold_in(pk, li), shape, fan_in)
                ),
                jnp.arange(ls),
            )
        put("ssm_conv_w", mat(sk[2], (ls, k_c, c_w), k_c))
        put(
            "ssm_conv_b",
            (jax.random.normal(sk[3], (ls, c_w), dtype=jnp.float32) * 0.02).astype(dtype),
        )
        put(
            "ssm_a_log",
            jnp.log(jax.random.uniform(sk[4], (ls, h_s), jnp.float32, 1.0, 16.0)),
        )
        dt0 = jnp.exp(
            jax.random.uniform(
                sk[5], (ls, h_s), jnp.float32, math.log(1e-3), math.log(1e-1)
            )
        )
        put("ssm_dt_bias", dt0 + jnp.log(-jnp.expm1(-dt0)))
        put("ssm_d", jnp.ones((ls, h_s), dtype=jnp.float32))
        put("ssm_norm", jnp.ones((ls, cfg.ssm_d_inner), dtype=dtype))
    if kind == FFN_DENSE_THEN_EXPERTS:
        # the experts of the layers after the dense prefix, MADE (and
        # handed to ``post``, which may quantize them) ONE LAYER AT A TIME:
        # one layer of one leaf at published sizes is most of a GB in
        # float32, and all of them at once would not fit beside the model.
        # Layer i of leaf k draws from fold_in(fold_in(keys[11], k), i).
        fe = cfg.d_expert
        for i, (name, shape, fan_in) in enumerate((
            ("we_gate", (*e, d, fe), d),
            ("we_up", (*e, d, fe), d),
            # the gain folded into the fan-in: 1 leaves the divisor as it was
            ("we_down", (*e, fe, d), fe / cfg.init_routed_gain**2),
        )):
            ek = jax.random.fold_in(keys[11], i)
            params[name] = jax.lax.map(
                lambda li, name=name, shape=shape, fan_in=fan_in, ek=ek: post(
                    name, mat(jax.random.fold_in(ek, li), shape, fan_in)
                ),
                jnp.arange(l_moe),
            )
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * fe
            sk = [jax.random.fold_in(keys[11], 3 + i) for i in range(3)]
            put("ws_gate", mat(sk[0], (l_moe, d, fs), d))
            put("ws_up", mat(sk[1], (l_moe, d, fs), d))
            put("ws_down", mat(sk[2], (l_moe, fs, d), fs))
    elif cfg.d_ff_expert:
        # the experts beside the dense FFNs, one set a layer, from the
        # twelfth key (no dense leaf uses it)
        fe = cfg.d_ff_expert
        ek = [jax.random.fold_in(keys[11], i) for i in range(3)]
        put("we_gate", mat(ek[0], (l, *e, d, fe), d))
        put("we_up", mat(ek[1], (l, *e, d, fe), d))
        put("we_down", mat(ek[2], (l, *e, fe, d), fe))
    elif cfg.n_experts:
        put("w_gate", mat(keys[5], (l, *e, d, f), d))
        put("w_up", mat(keys[6], (l, *e, d, f), d))
        put("w_down", mat(keys[7], (l, *e, f, d), f))
    final_norm = ones_or_zeros((d,))
    if cfg.init_final_norm_gain != 1.0:
        final_norm = final_norm * jnp.asarray(cfg.init_final_norm_gain, dtype)
    put("final_norm", final_norm)
    if cfg.qkv_bias:
        put("bq", jnp.zeros((l_attn, hq * dh), dtype=dtype))
        put("bk", jnp.zeros((l_attn, hkv * dh), dtype=dtype))
        put("bv", jnp.zeros((l_attn, hkv * dh), dtype=dtype))
    if cfg.n_experts:
        # never quantized; scored in float32 (_moe_route)
        put("router", mat(keys[9], (l_moe, d, cfg.router_outputs), d))
        if cfg.router_bias:
            # the spacing of even scores, so that the bias moves some
            # choices and not most: 1 / router_outputs of a softmax (1e-3
            # behind the one softmax router that has a bias, 768 wide),
            # 1e-2 between 64 sigmoid scores
            put(
                "router_bias",
                jax.random.normal(
                    jax.random.fold_in(keys[9], 1),
                    (l_moe, cfg.router_outputs),
                    dtype=jnp.float32,
                )
                * (1e-2 if cfg.router_scoring == "sigmoid" else 1e-3),
            )
    if not cfg.tie_embeddings:
        put("lm_head", mat(keys[8], (d, cfg.vocab_size), d))
    return params


def _activation(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


# Token-expert pairs one block of the grouped expert matmul holds: a
# block belongs to ONE expert and reads that expert's weights once, so an
# expert's pairs are padded to a multiple of the block. The least block
# is a sublane tile of rows; the most keeps a block's padding affordable.
# A block is one grid step (times its weight tiles) of the grouped kernel
# (ops/pallas_moe.py) or one trip of the loop (moe_impl).
MOE_BLOCK_ROWS = 8
MOE_BLOCK_ROWS_MAX = 128


def moe_block_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of a block for a call of ``tokens`` tokens (static: a shape):
    the pairs an expert expects under even routing, ``tokens x top_k /
    router outputs``, rounded up to a power of two within the two bounds.
    A decode step, or a 256-token chunk behind a 768-wide router (4 pairs
    an expert), keeps the least block and pads little; a 256-token chunk
    over 8 experts of which each token takes 2 expects 64 pairs an expert
    and reads each expert once or twice, not once per 8 pairs."""
    expected = tokens * cfg.top_k_experts / cfg.router_outputs
    rows = MOE_BLOCK_ROWS
    while rows < expected and rows < MOE_BLOCK_ROWS_MAX:
        rows *= 2
    return rows


def _expert_leaves(cfg: ModelConfig) -> Tuple[str, str, str]:
    """(gate, up, down) of the experts: under the FFN's own names where
    every layer's FFN is its experts, else beside the dense FFN's leaves."""
    if cfg.ffn_kind == FFN_EXPERTS:
        return ("w_gate", "w_up", "w_down")
    return ("we_gate", "we_up", "we_down")


# a dense prefix's FFN, and the shared expert of the layers after it: the
# stacked leaves that are as long as their own run of layers (run_blocks)
DENSE_FFN_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_EXPERT_LEAVES = ("ws_gate", "ws_up", "ws_down")


def expert_layer_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    """The stacked leaves of the expert layer, ``[L, ...]`` each. The layer
    scan does NOT slice them (``run_blocks``): a scanned slice of every
    expert's weights is a copy of them all, layer after layer, whoever is
    chosen. They reach :func:`_moe_parts` whole, with the layer's index,
    and an expert's weights are read where a block of pairs needs them."""
    if not cfg.n_experts:
        return ()
    bias = ("router_bias",) if cfg.router_bias else ()
    return _expert_leaves(cfg) + ("router",) + bias


def _layer_of(leaf, li):
    """Layer ``li`` (traced) of a small stacked leaf."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, axis=0, keepdims=False),
        leaf,
    )


def _moe_route(cfg: ModelConfig, h: jnp.ndarray, layer: Params):
    """Router over its WHOLE width for tokens ``h [T, D]``: scores in
    float32 (a softmax over the outputs, or a sigmoid of each:
    ``cfg.router_scoring``), the top-k chosen by score (+ the bias, where
    the model has one: it moves the choice, never the weight), weights the
    chosen scores, renormalised or not, times the scaling factor.
    Returns ``(top_i [T, k] int32, top_w [T, k] float32)``."""
    logits = jnp.einsum(
        "td,de->te",
        h.astype(jnp.float32),
        maybe_dequant(layer["router"], jnp.float32),
    )
    sigmoid = cfg.router_scoring == "sigmoid"
    probs = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    if cfg.router_bias:
        _, top_i = jax.lax.top_k(
            probs + layer["router_bias"].astype(jnp.float32),
            cfg.top_k_experts,
        )
        top_w = jnp.take_along_axis(probs, top_i, axis=-1)
    else:
        top_w, top_i = jax.lax.top_k(probs, cfg.top_k_experts)
    if cfg.renormalize_topk:
        total = jnp.sum(top_w, axis=-1, keepdims=True)
        # sigmoid scores can all be small: DeepSeek-V3's guard
        top_w = top_w / (total + 1e-20 if sigmoid else total)
    if cfg.routed_scaling_factor != 1.0:
        top_w = top_w * cfg.routed_scaling_factor
    return top_i, top_w


def _expert_ffn(cfg: ModelConfig, x: jnp.ndarray, leaves, li, e) -> jnp.ndarray:
    """``x [R, D]`` through held expert ``e`` of layer ``li`` (traced
    indices into the leaves' ``[L, E, ...]``): float32 ``[R, D]``. int8
    codes are read as stored (converted inside the matmul) and the
    expert's per-output-channel scales multiply the matmul's result."""

    def pick(a):
        return jax.lax.dynamic_slice(
            a, (li, e) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:]
        ).reshape(a.shape[2:])

    def dot(x, leaf):
        if is_quantized(leaf) and "q" in leaf:
            y = jnp.dot(
                x, pick(leaf["q"]).astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            return y * pick(leaf["s"])
        w = maybe_dequant(jax.tree_util.tree_map(pick, leaf), x.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32)

    gate, up, down = leaves
    act = _glu(cfg, dot(x, gate), lambda: dot(x, up))
    return dot(act.astype(x.dtype), down)


def moe_impl(cfg: ModelConfig, x_dtype, tokens: int, experts: Params) -> str:
    """Name of what the grouped expert FFN of a call of ``tokens`` tokens
    compiles to -- the ONE rule: :func:`_moe_parts` branches on it at
    trace time and a session's ``/debug/state`` reports it.
    ``"pallas-grouped"``: one pipelined kernel over all of the layer's
    blocks (``ops/pallas_moe.py``), where the leaves and shapes fit it
    (int8 or bfloat16 leaves, widths in lane tiles) and the trace is not
    a sharded engine's (the kernel has no partitioning rule). Else
    ``"xla-loop"``: a device loop of one gated FFN a block (packed int4
    leaves, float32 leaves, small unaligned shapes)."""
    leaves = (experts[name] for name in _expert_leaves(cfg))
    if unpartitioned_kernels_enabled() and grouped_ffn_supported(
        x_dtype, tokens, moe_block_rows(cfg, tokens), *leaves
    ):
        return "pallas-grouped"
    return "xla-loop"


def state_step_impl(cfg: ModelConfig, state, tokens: int) -> str:
    """What a call of ``tokens`` tokens a row does with the recurrent state
    record ``state``: the rule of the stack's recurrent mixer
    (models/ssm.py ``ssm_step_impl``, models/kda.py ``kda_step_impl``)."""
    if cfg.state_mixer == MIXER_KDA:
        return kda_step_impl(cfg, state, tokens)
    return ssm_step_impl(cfg, state, tokens)


def side_impl(cfg: ModelConfig, side, tokens: int) -> str:
    """Name of what the side part of a paged decode step of ``tokens``
    tokens a row compiles to -- the ONE rule: ``run_blocks`` branches on
    it at trace time and a session's ``/debug/state`` reports it.
    ``"pallas-live"``: one kernel a layer that writes the LIVE rows'
    token into the side caches and reads their columns up to it, where
    they lie (``ops/pallas_paged_side.py``), for a one-token step over a
    plain bfloat16 or float32 side leaf in a shape the kernel tiles, in a
    trace that is not a sharded engine's (the kernel has no partitioning
    rule). Else ``"xla"``: the token scattered in by ``_kv_write``, the
    layer's whole slice read and masked (the speculative verify's block
    of tokens, int8 sides, latent caches, which ``_latent_attend`` reads)."""
    if (
        tokens == 1
        and not cfg.latent
        and unpartitioned_kernels_enabled()
        and paged_side_supported(side)
    ):
        return "pallas-live"
    return "xla"


def _moe_parts(
    cfg: ModelConfig,
    h: jnp.ndarray,  # [B,S,D]
    experts: Params,  # expert_layer_leaves, stacked [L, ...]
    li,  # the layer's index into them (traced inside the layer scan)
    token_mask: Optional[jnp.ndarray] = None,  # [B,S] bool: tokens that count
):
    """The expert layer as this chip's share of it: ``(routed, identity,
    counts)``, both float32 ``[B,S,D]``. ``routed`` is what the experts
    HELD HERE add for the tokens whose choices land on them, ``identity``
    the identity experts' ``h * sum(w)``; a routed expert that is not held
    adds nothing. ``counts`` is int32 ``[5]``: pairs on held experts, on
    identity experts, on absent experts, held experts with at least one
    pair, and blocks (an expert's weights are read once a block). Masked-out
    tokens route nowhere and count nowhere.

    Dispatch is by token-expert pair, grouped by expert: each held expert's
    pairs, in token order, are cut into blocks of :func:`moe_block_rows`
    pairs, and one gated FFN runs per REAL block on the block's expert.
    ``tokens x top_k`` pairs is the static bound, so no token is dropped;
    an expert nobody chose is never read. How the blocks run is
    :func:`moe_impl`'s: one pipelined kernel over all of them, or a loop
    whose trip count is data."""
    b, s, d = h.shape
    t, k, n_held = b * s, cfg.top_k_experts, cfg.n_experts
    rows = moe_block_rows(cfg, t)
    hf = h.reshape(t, d)
    with jax.named_scope("moe.router"):
        top_i, top_w = _moe_route(
            cfg, hf,
            {k: _layer_of(v, li) for k, v in experts.items() if k.startswith("router")},
        )
    with jax.named_scope("moe.dispatch"):
        live = (
            jnp.ones((t, 1), dtype=bool)
            if token_mask is None
            else token_mask.reshape(t, 1)
        )
        local = top_i - cfg.first_expert
        held = (local >= 0) & (local < n_held) & live
        zero = (top_i >= cfg.n_routed_experts) & live
        key = jnp.where(held, local, n_held).reshape(-1)  # [T*k]
        chosen = jax.nn.one_hot(key, n_held + 1, dtype=jnp.int32)
        n_pairs = jnp.sum(chosen, axis=0)[:n_held]  # pairs per held expert
        n_blocks = (n_pairs + rows - 1) // rows
        block_end = jnp.cumsum(n_blocks)
        pair_start = jnp.cumsum(n_pairs) - n_pairs
        flat_w = top_w.reshape(-1)
        counts = jnp.stack([
            jnp.sum(held), jnp.sum(zero),
            jnp.sum(live) * k - jnp.sum(held) - jnp.sum(zero),
            jnp.sum(n_pairs > 0), block_end[-1],
        ]).astype(jnp.int32)
    leaves = tuple(experts[name] for name in _expert_leaves(cfg))
    if moe_impl(cfg, h.dtype, t, experts) == "pallas-grouped":
        routed = _moe_blocks_grouped(
            cfg, hf, leaves, li, rows, key, chosen, held.reshape(-1), flat_w,
            n_blocks, block_end,
        )
    else:
        with jax.named_scope("moe.dispatch"):
            order = jnp.argsort(key, stable=True).astype(jnp.int32)

        def block(j, out):
            with jax.named_scope("moe.dispatch"):
                # the expert whose run of blocks holds block j (a compare per
                # held expert, not a search: a scalar loop costs more on the chip)
                e = jnp.sum(block_end <= j).astype(jnp.int32)
                within = j - (block_end[e] - n_blocks[e])
                at = pair_start[e] + within * rows + jnp.arange(rows, dtype=jnp.int32)
                valid = at < pair_start[e] + n_pairs[e]
                pair = order[jnp.clip(at, 0, t * k - 1)]
                tok = pair // k
                x = hf[tok]  # [rows, D]
                w = jnp.where(valid, flat_w[pair], 0.0)
            with jax.named_scope("moe.experts"):
                y = _expert_ffn(cfg, x, leaves, li, e)
            with jax.named_scope("moe.combine"):
                # a token meets an expert once, so a block's real rows are
                # distinct tokens; its padding rows add zero
                return out.at[tok].add(y * w[:, None])

        with jax.named_scope("moe.experts"):
            routed = jax.lax.fori_loop(
                0, block_end[-1], block, jnp.zeros((t, d), dtype=jnp.float32)
            )
    with jax.named_scope("moe.zero"):
        identity = hf.astype(jnp.float32) * jnp.sum(
            jnp.where(zero, top_w, 0.0), axis=-1, keepdims=True
        )
    return routed.reshape(b, s, d), identity.reshape(b, s, d), counts


def _moe_blocks_grouped(
    cfg, hf, leaves, li, rows, key, chosen, held, flat_w, n_blocks, block_end,
):
    """The held experts' part, float32 ``[T, D]``, by ONE kernel over the
    real blocks (``ops/pallas_moe.py``), which gathers each block's rows
    from the tokens and adds each weighted result to its token. A pair's
    slot in the blocks' rows is its expert's first block times ``rows``
    plus its rank among that expert's pairs (a running count, no sort);
    the kernel is told each slot's token and weight. The static bound on
    blocks is one partial block a held expert and the full ones ``T x
    top_k`` pairs can fill; slots no pair owns weigh nothing."""
    t, k, n_held = hf.shape[0], cfg.top_k_experts, cfg.n_experts
    n_max = min(n_held, t * k) + (t * k) // rows
    with jax.named_scope("moe.dispatch"):
        rank = jnp.sum((jnp.cumsum(chosen, axis=0) - chosen) * chosen, axis=-1)
        first_block = block_end - n_blocks
        slot = jnp.where(
            held, first_block[jnp.minimum(key, n_held - 1)] * rows + rank, -1
        )  # [T*k]
        owner = slot[None, :] == jnp.arange(n_max * rows, dtype=jnp.int32)[:, None]
        slot_token = jnp.sum(
            jnp.where(owner, jnp.arange(t * k, dtype=jnp.int32) // k, 0), axis=-1
        )
        slot_weight = jnp.sum(jnp.where(owner, flat_w, 0.0), axis=-1)
        block_expert = jnp.minimum(
            jnp.sum(
                block_end[None, :] <= jnp.arange(n_max, dtype=jnp.int32)[:, None],
                axis=-1,
            ),
            n_held - 1,
        )
    with jax.named_scope("moe.experts"):
        return grouped_expert_ffn(
            hf, *leaves, li, block_expert, block_end[-1], slot_token,
            slot_weight, activation=cfg.activation, limit=cfg.swiglu_limit,
        )


def _moe_mlp(
    cfg: ModelConfig,
    h: jnp.ndarray,
    experts: Params,
    li,
    token_mask: Optional[jnp.ndarray] = None,
):
    """The expert layer (:func:`_moe_parts`): what the held experts and
    the identity experts add, ``[B,S,D]`` in ``h``'s dtype, and the
    routing counts. The Mixtral-style layer is its parametrisation: every
    routed expert held (``router_width`` 0), no identity experts, the
    top-k renormalised, factor 1. On a mesh ``parallel/sharding.py``
    places the expert axis over ``ep``, but a block reads its expert by
    index, so GSPMD fetches that expert to every device: the numbers are
    the unsharded ones (tests/test_moe.py), the traffic is not that of an
    expert-parallel exchange, which is not built (ROADMAP); a sharded
    engine's traces run the loop, since the grouped kernel has no
    partitioning rule (:func:`moe_impl`)."""
    routed, identity, counts = _moe_parts(cfg, h, experts, li, token_mask)
    with jax.named_scope("moe.combine"):
        if cfg.n_zero_experts:
            routed = routed + identity
        return routed.astype(h.dtype), counts


def _glu(cfg: ModelConfig, g: jnp.ndarray, up: Callable[[], jnp.ndarray]) -> jnp.ndarray:
    """``act(g) * up()``; with ``swiglu_limit`` the gate clamped from above
    and the up projection both ways first."""
    lim = cfg.swiglu_limit
    a = _activation(cfg, jnp.minimum(g, lim) if lim else g)
    u = up()
    return a * (jnp.clip(u, -lim, lim) if lim else u)


def _gated_ffn(cfg: ModelConfig, h: jnp.ndarray, gate, up, down) -> jnp.ndarray:
    """A dense gated FFN: ``(act(h G) * (h U)) D``."""
    return dense_dot(_glu(cfg, dense_dot(h, gate), lambda: dense_dot(h, up)), down)


def _hc_map(cfg: ModelConfig, x: jnp.ndarray, hc: Params):
    """The residual-stream map of ONE sublayer (manifold-constrained
    hyper-connections) for the state ``x [B,S,n,D]``, in float32:
    ``(h_pre [B,S,n], h_post [B,S,n], h_res)``, ``h_res`` the ``n x n``
    mixing matrix as ``n`` rows of ``n`` arrays ``[B,S]``.

    ``m = norm(vec(x)) phi`` (no gain on the norm: one would fold into
    ``phi``); the read weights ``sigmoid(a_pre m[:n] + b_pre)``, the write
    weights ``2 sigmoid(a_post m[n:2n] + b_post)``, and the mixing logits
    ``a_res m[2n:] + b_res`` (row-major), clamped to ``+-hc_res_clamp``,
    exponentiated and Sinkhorn-projected: ``hc_sinkhorn_iters`` times the
    columns and then the rows divided by their sums (+ ``hc_eps``). The
    projection is written on the matrix's ``n x n`` entries as arrays of
    one shape, elementwise throughout, so that the whole of it can fuse
    into one loop on the device instead of two small reductions a turn."""
    n = cfg.residual_streams
    b, s, _, d = x.shape
    f32 = jnp.float32
    with jax.named_scope("hc.map"):
        v = x.astype(f32).reshape(b, s, n * d)
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + cfg.hc_eps)
        m = jnp.einsum("bsv,vo->bso", v, hc["phi"], precision=jax.lax.Precision.HIGHEST)
        alpha, bias = hc["alpha"], hc["bias"]
        h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n : 2 * n] + bias[n : 2 * n])
        z = jnp.clip(alpha[2] * m[..., 2 * n :] + bias[2 * n :], -cfg.hc_res_clamp, cfg.hc_res_clamp)
        e = jnp.exp(z)
        rows = [[e[..., i * n + j] for j in range(n)] for i in range(n)]
        for _ in range(cfg.hc_sinkhorn_iters):
            inv = [1.0 / (sum(rows[i][j] for i in range(n)) + cfg.hc_eps) for j in range(n)]
            rows = [[rows[i][j] * inv[j] for j in range(n)] for i in range(n)]
            inv = [1.0 / (sum(row) + cfg.hc_eps) for row in rows]
            rows = [[a * inv[i] for a in row] for i, row in enumerate(rows)]
    return h_pre, h_post, rows


def _hc_read(x: jnp.ndarray, h_pre: jnp.ndarray) -> jnp.ndarray:
    """The sublayer's input off the streams: ``sum_i h_pre[i] x[i]``."""
    with jax.named_scope("hc.pre"):
        xf = x.astype(jnp.float32)
        u = sum(h_pre[..., i, None] * xf[:, :, i] for i in range(x.shape[2]))
        return u.astype(x.dtype)


def _hc_write(x: jnp.ndarray, h_post: jnp.ndarray, h_res, y: jnp.ndarray) -> jnp.ndarray:
    """The streams after the sublayer: ``x'[i] = sum_j h_res[i][j] x[j] +
    h_post[i] y``."""
    with jax.named_scope("hc.post"):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        streams = [xf[:, :, j] for j in range(len(h_res))]
        out = [
            sum(w[..., None] * xj for w, xj in zip(row, streams))
            + h_post[..., i, None] * yf
            for i, row in enumerate(h_res)
        ]
        return jnp.stack(out, axis=2).astype(x.dtype)


def _kv_write(k_cache, v_cache, k, v, offset):
    """Write this block's K/V ([B,S,Hkv,Dh] each) into whichever cache
    layout the step runs on; returns the updated cache leaves."""
    b, s = k.shape[:2]
    quant_cache = is_quantized_cache(k_cache)
    paged_cache = is_paged_cache(k_cache)
    carry_cache = is_carry_cache(k_cache)
    if paged_cache:
        # Write this token's K/V at each row's (page, slot) through the
        # page table — the block-table indirection that lets mixed-length
        # requests share one pool. The addressing arithmetic lives in ONE
        # place (engine/paged_kv.page_slot) shared with the row-level
        # helpers, so the two writers cannot drift.
        table = k_cache["table"]  # [B, Jmax]
        if "side" in k_cache:
            # STACKED-HYBRID mode: the pool is READ-ONLY during decode
            # (prefill pages only); this step's K/V row lands in the
            # contiguous side cache at the row's generated-token index —
            # the cheap arange-rows write the contiguous batched path
            # uses. (Both pool-write alternatives measured a full pool
            # copy on real hardware: per-STEP via scan ys, per-LAYER via
            # a traced-layer scatter — docs/PERF.md.) With "side_layer"
            # the side is the whole [L,B,Hkv,Tgen,D] stack riding the
            # decode carry (is_carry_cache rationale: scan ys wrote back
            # the full per-layer side every layer), and only this
            # token's row is written at [layer, row, :, wp]. An int8-KV
            # engine's side caches are {"q","s"} dicts: the step's
            # vector quantizes with the decode-step scale math
            # (quantize_kv_vector) so generated tokens see the same
            # quantization as the contiguous int8 path's. S > 1 is the
            # speculative VERIFY block (ISSUE 10): the k+1 candidates
            # land at [row, :, wp+j] — the side cache doubles as the
            # verify scratch, rejected tails are simply overwritten by
            # the next round's block, and the POOL is never touched, so
            # paged spec rows bill no slack pages.
            rows = jnp.arange(b)
            wp = k_cache["write_pos"]  # [B]
            if s == 1:
                row_idx, pos_idx = rows, wp  # [B] each — the hot path
            else:
                row_idx = rows[:, None]  # [B,1]
                pos_idx = wp[:, None] + jnp.arange(s, dtype=jnp.int32)

            def side_write(cache, vec):  # vec [B,Hkv,D] or [B,S,Hkv,D]
                side = cache["side"]
                sli = cache.get("side_layer")
                if isinstance(side, dict):
                    q_, s_ = quantize_kv_vector(vec)
                    if sli is not None:
                        new = {
                            "q": side["q"].at[sli, row_idx, :, pos_idx].set(q_),
                            "s": side["s"].at[sli, row_idx, :, pos_idx].set(s_),
                        }
                    else:
                        new = {
                            "q": side["q"].at[row_idx, :, pos_idx].set(q_),
                            "s": side["s"].at[row_idx, :, pos_idx].set(s_),
                        }
                elif sli is not None:
                    new = side.at[sli, row_idx, :, pos_idx].set(
                        vec.astype(side.dtype)
                    )
                else:
                    new = side.at[row_idx, :, pos_idx].set(
                        vec.astype(side.dtype)
                    )
                return {**cache, "side": new}

            if "live" not in k_cache:  # else the side kernel writes it
                k_cache = side_write(k_cache, k[:, 0] if s == 1 else k)
                v_cache = side_write(v_cache, v[:, 0] if s == 1 else v)
        elif "scratch" in k_cache:
            # SCRATCH verify mode (kernel-less paged sessions, ISSUE
            # 10): the block's candidate K/V replace the small per-layer
            # scratch wholesale — [B,Hkv,S,D], a mini contiguous cache
            # so the TP payload sharding rule applies verbatim. The pool
            # is read-only here; engine/speculative.py commits the
            # accepted prefix through the page table AFTER acceptance,
            # with the identical quantization a plain decode step's
            # pool write would apply (the codes below ARE what commit
            # copies, so candidates attend to each other through the
            # same quantized values the old eager write produced).
            def scratch_write(cache, vec):  # vec [B,S,Hkv,D]
                vt = vec.transpose(0, 2, 1, 3)  # [B,Hkv,S,D]
                if isinstance(cache["scratch"], dict):
                    q_, s_ = quantize_kv_vector(vt)
                    return {**cache, "scratch": {"q": q_, "s": s_}}
                return {
                    **cache,
                    "scratch": vt.astype(cache["scratch"].dtype),
                }

            k_cache = scratch_write(k_cache, k)
            v_cache = scratch_write(v_cache, v)
        else:
            pool_k_leaf = k_cache["pool"]
            page_size = (
                pool_k_leaf["q"]
                if isinstance(pool_k_leaf, dict)
                else pool_k_leaf
            ).shape[-2]
            off_b = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
            # Positions of this block's tokens: [B, S] (S == 1 always —
            # multi-token blocks ride the side/scratch leaves above; the
            # eager pool-write verify is gone, ISSUE 10). The page/slot
            # arithmetic is page_slot's rule applied per position; a
            # row's positions never collide (distinct slots) and rows
            # own disjoint pages, so the one scatter is exact.
            pos = off_b[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            pages = jnp.take_along_axis(
                jnp.asarray(table, jnp.int32), pos // page_size, axis=-1
            )  # [B, S]
            slots = pos % page_size

            def pool_write(cache, vec):  # vec [B,S,Hkv,D]
                pool = cache["pool"]
                if isinstance(pool, dict):  # int8 pages: codes + scale
                    q_, s_ = quantize_kv_vector(vec)
                    new = {
                        "q": pool["q"].at[pages, :, slots].set(q_),
                        "s": pool["s"].at[pages, :, slots].set(s_),
                    }
                else:
                    new = pool.at[pages, :, slots].set(
                        vec.astype(pool.dtype)
                    )
                return {**cache, "pool": new}

            k_cache = pool_write(k_cache, k)
            v_cache = pool_write(v_cache, v)
    elif quant_cache:
        # Quantize the new entries and write codes + per-vector scales.
        # Only the solo (scalar-offset) path reaches here: batched
        # per-seq decode over quantized caches is intercepted by
        # run_blocks' carry branch, whose quantized carry write above
        # does the per-row [layer, row, :, offset] update. S > 1 is the
        # solo speculative VERIFY block (k+1 positions quantized with
        # the same per-vector scale math a step-at-a-time decode would
        # use, so the accepted tokens see bit-identical cache entries).
        kq, ks = quantize_kv_vector(k.transpose(0, 2, 1, 3))  # [B,Hkv,S,dh]
        vq, vs = quantize_kv_vector(v.transpose(0, 2, 1, 3))
        k_cache = {
            "q": jax.lax.dynamic_update_slice(
                k_cache["q"], kq, (0, 0, offset, 0)
            ),
            "s": jax.lax.dynamic_update_slice(
                k_cache["s"], ks, (0, 0, offset)
            ),
        }
        v_cache = {
            "q": jax.lax.dynamic_update_slice(
                v_cache["q"], vq, (0, 0, offset, 0)
            ),
            "s": jax.lax.dynamic_update_slice(
                v_cache["s"], vs, (0, 0, offset)
            ),
        }
    elif carry_cache:
        # Tiny in-place writes into the stacked carry at [layer, row, :,
        # offset + j] — the whole point of the carry-resident design (no
        # per-layer write-back of the untouched 25 MB slice). S == 1 for
        # plain decode; S == k+1 is the batched speculative VERIFY block
        # (each row's candidate positions land at its own offsets — one
        # scatter, no index collisions since rows are disjoint).
        # Quantized carries write codes + per-vector scales the same way
        # the per-layer quant branch below does.
        li = k_cache["layer"]
        rows = jnp.arange(b)
        if s == 1:
            row_idx, pos_idx = rows, offset  # [B] each — the hot path
            kt, vt = k[:, 0], v[:, 0]  # [B,Hkv,dh]
        else:
            row_idx = rows[:, None]  # [B,1]
            pos_idx = offset[:, None] + jnp.arange(s, dtype=jnp.int32)
            kt, vt = k, v  # [B,S,Hkv,dh]
        if isinstance(k_cache["all"], dict):
            kq, ksc = quantize_kv_vector(kt)
            vq, vsc = quantize_kv_vector(vt)
            k_cache = {
                "layer": li,
                "all": {
                    "q": k_cache["all"]["q"].at[li, row_idx, :, pos_idx].set(kq),
                    "s": k_cache["all"]["s"].at[li, row_idx, :, pos_idx].set(ksc),
                },
            }
            v_cache = {
                "layer": li,
                "all": {
                    "q": v_cache["all"]["q"].at[li, row_idx, :, pos_idx].set(vq),
                    "s": v_cache["all"]["s"].at[li, row_idx, :, pos_idx].set(vsc),
                },
            }
        else:
            k_cache = {
                "layer": li,
                "all": k_cache["all"]
                .at[li, row_idx, :, pos_idx]
                .set(kt.astype(k_cache["all"].dtype)),
            }
            v_cache = {
                "layer": li,
                "all": v_cache["all"]
                .at[li, row_idx, :, pos_idx]
                .set(vt.astype(v_cache["all"].dtype)),
            }
    else:
        # Scalar-offset (solo / prefill) contiguous write. Batched
        # per-seq decode over plain caches never reaches here: run_blocks
        # routes it to the carry branch above (per-row writes land at
        # [layer, row, :, offset] in the stacked carry).
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype), (0, 0, offset, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype), (0, 0, offset, 0)
        )
    return k_cache, v_cache


def _attend(
    cfg: ModelConfig,
    q: jnp.ndarray,  # [B,S,Hq,Dh], roped
    k_cache,
    v_cache,
    offset: jnp.ndarray,
    t: int,  # attended cache length
    dtype,  # the block's activation dtype
    decode_attention: Optional[DecodeAttentionFn],
    prefill_attention: Optional[PrefillAttentionFn],
    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Any, Any]:
    """Scores, softmax and values over the cache (this block's entries
    already written, but for the side kernel's: it writes ``kv``, the
    block's K/V [B,S,Hkv,Dh], itself), or the kernel that does them:
    [B,S,Hq,Dh], and the caches as they leave it. The reads that
    materialise cached K/V for XLA's path (the layer's slice of a carry,
    the side caches, the paged gather, a dequant) carry the scope
    ``attn.kv_gather``."""
    b, s = q.shape[:2]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    paged_cache = is_paged_cache(k_cache)
    carry_cache = is_carry_cache(k_cache)
    per_seq = jnp.ndim(offset) == 1
    scale = 1.0 / math.sqrt(dh)
    scale = 1.0 / math.sqrt(dh)
    # Attention reads: carry-resident caches attend over their layer's
    # slice of the stacked carry (the read is inherent — attention
    # consumes the whole slice; only the write-back was waste).
    if carry_cache:

        def _layer_view(leaf):
            sl = functools.partial(
                jax.lax.dynamic_index_in_dim,
                index=leaf["layer"],
                axis=0,
                keepdims=False,
            )
            if isinstance(leaf["all"], dict):  # int8-KV: codes + scales
                return {"q": sl(leaf["all"]["q"]), "s": sl(leaf["all"]["s"])}
            return sl(leaf["all"])

        with jax.named_scope("attn.kv_gather"):
            k_att = _layer_view(k_cache)
            v_att = _layer_view(v_cache)
    else:
        k_att, v_att = k_cache, v_cache
    if (
        decode_attention is not None
        and paged_cache
        and "side" in k_cache
    ):
        # Stacked-hybrid paged decode: the kernel emits unnormalised
        # (acc, m, l) over the PROMPT pages (static lengths — the pool
        # never changes during the loop); the generated tokens, including
        # this step's (written above), attend through the side cache:
        # with "live" (side_impl's "pallas-live") one kernel reads the
        # live rows' written columns where they lie, else XLA reads the
        # layer's whole slice and masks it; the two online-softmax parts
        # merge exactly. S > 1 is the
        # speculative verify block: the engine's wrapper dispatches the
        # [B,S,Hq,D] query to the MULTI-QUERY parts kernel (ISSUE 10) —
        # one pass streams each row's pages once for all k+1 candidate
        # positions — and the side merge applies the per-query causal
        # cut ``tpos <= wp[b] + j`` (the candidates written above ARE
        # their own in-block context).
        group = hq // hkv
        wp = k_cache["write_pos"]

        def side_view(cache):  # → f32 [B,Hkv,Tgen,D]
            side = cache["side"]
            sli = cache.get("side_layer")
            if sli is not None:  # carry-resident: this layer's slice
                take = functools.partial(
                    jax.lax.dynamic_index_in_dim,
                    index=sli, axis=0, keepdims=False,
                )
            else:
                take = lambda a: a  # noqa: E731
            if isinstance(side, dict):  # int8 side: dequant the slice
                return take(side["q"]).astype(jnp.float32) * take(
                    side["s"]
                ).astype(jnp.float32)[..., None]
            return take(side).astype(jnp.float32)

        def side_views():  # -> f32 [B,Hkv,Tgen,D] twice, column index
            with jax.named_scope("attn.kv_gather"):
                ks = side_view(k_cache)
                vs = side_view(v_cache)
            return ks, vs, jnp.arange(ks.shape[2])

        if "live" in k_cache:  # side_impl's "pallas-live"
            acc1, m1, l1 = decode_attention(
                q[:, 0], k_cache, v_cache, k_cache["prompt_lens"]
            )
            acc2, m2, l2, k_side, v_side = paged_side_parts(
                q[:, 0], kv[0][:, 0], kv[1][:, 0], k_cache["side"],
                v_cache["side"], k_cache["side_layer"], wp, *k_cache["live"],
            )
            # the side leaves with this step's token in them, where they lie
            k_cache = {**k_cache, "side": k_side}
            v_cache = {**v_cache, "side": v_side}
        elif s == 1:
            ks, vs, tpos = side_views()
            acc1, m1, l1 = decode_attention(
                q[:, 0], k_cache, v_cache, k_cache["prompt_lens"]
            )
            qg = q[:, 0].reshape(b, hkv, group, dh).astype(jnp.float32)
            s2 = jnp.einsum("bkgd,bktd->bkgt", qg, ks) * scale
            s2 = jnp.where(
                (tpos[None, :] <= wp[:, None])[:, None, None, :],
                s2,
                -jnp.inf,
            )
            m2 = jnp.max(s2, axis=-1)  # finite: the current token is col wp
            p2 = jnp.exp(s2 - m2[..., None])
            l2 = jnp.sum(p2, axis=-1)
            acc2 = jnp.einsum("bkgt,bktd->bkgd", p2, vs)
        else:
            ks, vs, tpos = side_views()
            acc1, m1, l1 = decode_attention(
                q, k_cache, v_cache, k_cache["prompt_lens"]
            )  # [B,S,Hkv,G,D] / [B,S,Hkv,G] — per query position
            acc1 = acc1.transpose(0, 2, 3, 1, 4)  # [B,Hkv,G,S,D]
            m1 = m1.transpose(0, 2, 3, 1)  # [B,Hkv,G,S]
            l1 = l1.transpose(0, 2, 3, 1)
            qg = q.reshape(b, s, hkv, group, dh).astype(jnp.float32)
            s2 = jnp.einsum("bskgd,bktd->bkgst", qg, ks) * scale
            vis = (
                tpos[None, None, :]
                <= (wp[:, None] + jnp.arange(s))[:, :, None]
            )  # [B,S,Tgen]
            s2 = jnp.where(vis[:, None, None], s2, -jnp.inf)
            m2 = jnp.max(s2, axis=-1)  # [B,Hkv,G,S] — finite (col wp+j)
            p2 = jnp.exp(s2 - m2[..., None])
            l2 = jnp.sum(p2, axis=-1)
            acc2 = jnp.einsum("bkgst,bktd->bkgsd", p2, vs)
        m_t = jnp.maximum(m1, m2)
        w1 = jnp.exp(m1 - m_t)  # 0 for empty prompts (m1=-inf)
        w2 = jnp.exp(m2 - m_t)
        out = (acc1 * w1[..., None] + acc2 * w2[..., None]) / (
            l1 * w1 + l2 * w2
        )[..., None]
        if s == 1:
            out = out.reshape(b, 1, hq, dh).astype(dtype)
        else:  # [B,Hkv,G,S,D] → [B,S,Hq,D]
            out = (
                out.transpose(0, 3, 1, 2, 4)
                .reshape(b, s, hq, dh)
                .astype(dtype)
            )
    elif s == 1 and decode_attention is not None:
        lengths = jnp.broadcast_to(offset + 1, (b,)).astype(jnp.int32)
        out = decode_attention(q[:, 0], k_att, v_att, lengths)  # [B,Hq,Dh]
        out = out[:, None]  # [B,1,Hq,Dh]
    elif s > 1 and prefill_attention is not None:
        out = prefill_attention(q, k_att, v_att, offset)  # [B,S,Hq,Dh]
    elif paged_cache and "scratch" in k_cache:
        # SCRATCH verify (kernel-less paged mode, ISSUE 10): the gather
        # fallback materialises the pool's CACHED tokens only — columns
        # past a row's offset were never written (candidates no longer
        # stream through the table) — and the block's own candidates
        # attend from the scratch at their absolute positions
        # ``offset[b]+i``, visible to query j iff ``i <= j`` (a fixed
        # lower-triangular block mask). Same math the eager-write verify
        # computed, with the pool left untouched.
        group = hq // hkv
        qg = q.reshape(b, s, hkv, group, dh).astype(jnp.float32)

        def scratch_view(leaf):  # → f32 [B,Hkv,S,D]
            scr = leaf["scratch"]
            if isinstance(scr, dict):
                return scr["q"].astype(jnp.float32) * scr["s"].astype(
                    jnp.float32
                )[..., None]
            return scr.astype(jnp.float32)

        with jax.named_scope("attn.kv_gather"):
            kf = jnp.concatenate(
                [_gather_paged(k_cache), scratch_view(k_cache)], axis=2
            )
            vf = jnp.concatenate(
                [_gather_paged(v_cache), scratch_view(v_cache)], axis=2
            )
        scores = jnp.einsum("bskgd,bktd->bkgst", qg, kf) * scale
        kpos = jnp.arange(t)
        pool_vis = jnp.broadcast_to(
            (kpos[None, :] < offset[:, None])[:, None, :], (b, s, t)
        )
        tri = jnp.broadcast_to(
            jnp.tril(jnp.ones((s, s), dtype=bool))[None], (b, s, s)
        )
        mask = jnp.concatenate([pool_vis, tri], axis=2)  # [B,S,T+S]
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,bktd->bskgd", probs, vf).reshape(
            b, s, hq, dh
        )
    else:
        group = hq // hkv
        qg = q.reshape(b, s, hkv, group, dh).astype(jnp.float32)
        with jax.named_scope("attn.kv_gather"):
            if paged_cache:
                kf = _gather_paged(k_cache)  # raises on stacked leafs
                vf = _gather_paged(v_cache)
            else:
                # the view is a {"q","s"} dict when the cache is quantized
                # (directly or through a carry leaf)
                kf = (
                    dequant_cache(k_att)
                    if isinstance(k_att, dict)
                    else k_att.astype(jnp.float32)
                )
                vf = (
                    dequant_cache(v_att)
                    if isinstance(v_att, dict)
                    else v_att.astype(jnp.float32)
                )
        scores = jnp.einsum("bskgd,bktd->bkgst", qg, kf) * scale
        kpos = jnp.arange(t)
        if per_seq:
            # per-row causal mask [B,S,T]: query j of row b sees
            # kpos <= offset[b] + j (S == 1 for plain batched decode;
            # S == k+1 for the speculative verify block, whose own
            # candidate entries — written above — ARE its context)
            qpos = offset[:, None] + jnp.arange(s, dtype=jnp.int32)
            mask = kpos[None, None, :] <= qpos[:, :, None]
        else:
            qpos = offset + jnp.arange(s)[:, None]
            # causal + only-written-prefix, in one predicate: [1,S,T]
            mask = (kpos[None, :] <= qpos)[None]
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,bktd->bskgd", probs, vf).reshape(b, s, hq, dh)
    return out, k_cache, v_cache


def _attention_block(
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B,S,D]
    layer: Params,
    k_cache: jnp.ndarray,  # [B,Hkv,T,Dh] — T-contiguous per head for DMA-friendly decode
    v_cache: jnp.ndarray,
    offset: jnp.ndarray,  # int32: write position of token 0 — scalar, or [B] (decode only)
    cos: jnp.ndarray,  # [B,S,half]
    sin: jnp.ndarray,
    decode_attention: Optional[DecodeAttentionFn],
    prefill_attention: Optional[PrefillAttentionFn] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    if cfg.latent:
        return _latent_attention_block(
            cfg, x, layer, k_cache, v_cache, offset, cos, sin,
            decode_attention,
        )
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    quant_cache = is_quantized_cache(k_cache)
    paged_cache = is_paged_cache(k_cache)
    carry_cache = is_carry_cache(k_cache)
    if paged_cache:
        # pool is [P,Hkv,page,D] (per-layer) or [L,P,Hkv,page,Dp]
        # (stacked) — possibly an int8 {"q","s"} dict (codes share the
        # bf16 layout): the page dim is [-2] in all forms
        pool_codes = (
            k_cache["pool"]["q"]
            if isinstance(k_cache["pool"], dict)
            else k_cache["pool"]
        )
        t = k_cache["table"].shape[1] * pool_codes.shape[-2]
    elif carry_cache:
        _all = k_cache["all"]
        t = (_all["q"] if isinstance(_all, dict) else _all).shape[3]
    else:
        t = (k_cache["q"] if quant_cache else k_cache).shape[2]
    per_seq = jnp.ndim(offset) == 1  # batched decode: one offset per sequence
    # Multi-token blocks at per-row offsets are the speculative VERIFY
    # forward (one target pass scores a row's k+1 candidate positions —
    # engine/speculative.py): supported on every decode-era cache
    # layout. On paged caches the candidates stay OUT of the pool during
    # verify (ISSUE 10): the stacked-hybrid mode writes them into its
    # side caches (the multi-query parts kernel streams the prompt pages
    # once for all k+1 positions), the kernel-less mode into the scratch
    # leaf — the eager pool-write verify, whose out-of-budget candidate
    # writes forced 2k+2 slack token slots of page billing, is deleted.
    if per_seq and s != 1 and paged_cache and set(k_cache) == {
        "pool", "table"
    }:
        raise ValueError(
            "paged multi-token verify rides the side caches (stacked-"
            "hybrid, multi-query kernel) or the scratch leaf (kernel-"
            "less) - the eager pool-write verify was removed (ISSUE 10)"
        )
    if carry_cache and not per_seq:
        raise ValueError(
            "carry-resident caches support batched per-row-offset decode only"
        )
    if quant_cache and s != 1 and per_seq:
        raise ValueError(
            "quantized contiguous caches take multi-token blocks at a "
            "shared scalar offset only (the solo speculative verify); "
            "batched per-row verify rides the carry-resident layout"
        )
    if paged_cache and s != 1 and not per_seq:
        raise ValueError(
            "paged KV caches support decode only (prefill runs contiguous "
            "and is scattered into the pool afterwards)"
        )

    with jax.named_scope("attn.norm_qkv"):
        q = dense_dot(x, layer["wq"])
        k = dense_dot(x, layer["wk"])
        v = dense_dot(x, layer["wv"])
        if cfg.qkv_bias:
            q = q + layer["bq"]
            k = k + layer["bk"]
            v = v + layer["bv"]
        q = q.reshape(b, s, hq, dh)
        k = k.reshape(b, s, hkv, dh)
        v = v.reshape(b, s, hkv, dh)
        if cfg.position_embedding == "rope":
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if cfg.attention_multiplier:
            # every score path below (XLA and kernels alike) multiplies by
            # one over the root of d_head: the configured scale's ratio to
            # that goes into the query, in float32
            q = (
                q.astype(jnp.float32)
                * (cfg.attention_multiplier * math.sqrt(dh))
            ).astype(q.dtype)

    with jax.named_scope("attn.kv_write"):
        k_cache, v_cache = _kv_write(k_cache, v_cache, k, v, offset)
    with jax.named_scope("attn.core"):
        out, k_cache, v_cache = _attend(
            cfg, q, k_cache, v_cache, offset, t, x.dtype,
            decode_attention, prefill_attention, (k, v),
        )
    with jax.named_scope("attn.out"):
        out = out.astype(x.dtype).reshape(b, s, hq * dh)
        return (
            dense_dot(out, layer["wo"]),
            k_cache,
            v_cache,
        )


def _kvb_parts(cfg: ModelConfig, leaf, dtype):
    """The latent up-projection ``W_kvb [rkv, H * (nope + v)]`` as its key
    and value halves ``[rkv, H, nope]`` and ``[rkv, H, v]``, each with its
    per-output-channel scales ``[H, nope]`` / ``[H, v]`` (None for plain
    weights): int8 codes are read as stored, and a scale multiplies the
    side of the contraction its channel is on."""
    h, n = cfg.n_heads, cfg.qk_nope_head_dim
    if is_quantized(leaf) and "q" in leaf:
        w = leaf["q"].reshape(cfg.kv_lora_rank, h, -1).astype(dtype)
        sc = leaf["s"].reshape(h, -1)
        return w[..., :n], sc[:, :n], w[..., n:], sc[:, n:]
    w = maybe_dequant(leaf, dtype).reshape(cfg.kv_lora_rank, h, -1)
    return w[..., :n], None, w[..., n:], None


def latent_score_scale(cfg: ModelConfig) -> float:
    """What a latent attention score is multiplied by: one over the root
    of the query head, times what the rotary scaling asks (YaRN)."""
    return rope_score_scale(cfg.rope_scaling) / math.sqrt(cfg.d_head)


def _index_keep(cfg, index, keys, qpos):
    """Where a query attends under the lightning indexer: ``[B,S,T]`` bool.
    ``index`` is ``(iq [B,S,Hi,Di], iw [B,S,Hi])`` float32, ``keys [B,T,Di]``
    the index keys at positions ``0 .. T-1`` (``T`` a multiple of
    ``index_kpool``), ``qpos [B,S]`` the queries' positions. Block ``n``
    pools positions ``n kpool .. (n + 1) kpool - 1`` (their keys' mean) and
    scores ``sum_j iw_j relu(iq_j . pooled_n)``; of the blocks a query has
    whole (``n < qpos // kpool``) it keeps the ``index_topk`` best (every
    block that scores at least the ``index_topk``-th best: all of them
    while there are no more), and its own block, the tail, always; within
    them the positions up to its own."""
    iq, iw = index
    b, t, _ = keys.shape
    kp = cfg.index_kpool
    nb = t // kp
    pooled = jnp.mean(keys.reshape(b, nb, kp, -1), axis=2)
    scores = jnp.einsum(
        "bshd,bnd->bshn", iq, pooled, precision=jax.lax.Precision.HIGHEST
    )
    score = jnp.sum(iw[..., None] * jax.nn.relu(scores), axis=2)  # [B,S,NB]
    tail = (qpos // kp)[..., None]
    block = jnp.arange(nb, dtype=jnp.int32)
    whole = block < tail
    score = jnp.where(whole, score, -jnp.inf)
    kth = jax.lax.top_k(score, min(cfg.index_topk, nb))[0][..., -1:]
    keep = (whole & (score >= kth)) | (block == tail)
    keep = jnp.repeat(keep, kp, axis=-1)
    return keep & (jnp.arange(t, dtype=jnp.int32) <= qpos[..., None])


def _index_keys(cfg, rows):
    """The index keys of cached rows ``[..., cache_k_width]``, float32."""
    lo = cfg.latent_row_width
    return rows[..., lo : lo + cfg.index_head_dim].astype(jnp.float32)


def _paged_index_keys(cfg, k_cache):
    """``[B, T, Di]`` float32: every row's index keys by position, the
    prompt's off its pool pages, the generated tokens' off its side rows
    (``T`` = the table's columns + the side's, rounded up to the pool's
    blocks)."""
    pool, table = k_cache["pool"], k_cache["table"]
    b, jmax = table.shape
    page = pool.shape[-2]
    plens = k_cache["prompt_lens"]
    side = jax.lax.dynamic_index_in_dim(
        k_cache["side"], k_cache["side_layer"], axis=0, keepdims=False
    )[:, 0]  # [B, Tgen, width]
    t_pool, t_side = jmax * page, side.shape[1]
    t = -(-(t_pool + t_side) // cfg.index_kpool) * cfg.index_kpool
    pages = pool[jnp.clip(table.astype(jnp.int32), 0, pool.shape[0] - 1)]
    prompt = _index_keys(cfg, pages[:, :, 0]).reshape(b, t_pool, -1)
    prompt = jnp.pad(prompt, ((0, 0), (0, t - t_pool), (0, 0)))
    pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    at = jnp.clip(pos - plens[:, None], 0, t_side - 1)
    gen = jnp.take_along_axis(_index_keys(cfg, side), at[..., None], axis=1)
    return jnp.where((pos < plens[:, None])[..., None], prompt, gen)


def _latent_attend(cfg, q, k_cache, offset, decode_attention, index=None):
    """Attention in the ABSORBED latent form: queries ``q [B,S,H,rkv +
    rope]`` float32 (``W_kvb``'s key half already folded in) against the
    cached rows as ONE kv head of group ``H``; keys are a row's
    ``rkv + rope`` values, values its first ``kv_lora_rank`` columns.
    Returns float32 ``[B,S,H,rkv]``. Serves the contiguous cache (prefill
    and decode, a shared scalar offset), the carry-resident stack (batched
    decode) and the stacked-hybrid paged session (``decode_attention``
    gives the prompt pages' unnormalised parts, the side cache merges
    here). ``index`` (a model with a lightning indexer: its queries and
    head weights) limits every query to the positions :func:`_index_keep`
    keeps, off the index keys the rows carry after their values."""
    b, s, hq, _ = q.shape
    rkv = cfg.kv_lora_rank
    row_w = cfg.latent_row_width
    scale = latent_score_scale(cfg)
    qg = q.reshape(b, s, 1, hq, q.shape[-1])

    def layer_view(cache, rows_key, index_key):  # -> f32 [B,1,T,width]
        return jax.lax.dynamic_index_in_dim(
            cache[rows_key], cache[index_key], axis=0, keepdims=False
        ).astype(jnp.float32)

    if is_paged_cache(k_cache):
        if "side" not in k_cache or s != 1 or decode_attention is None:
            raise ValueError(
                "a latent cache is paged in the stacked-hybrid layout only "
                "(single-token decode over prompt pages + side rows)"
            )
        wp = k_cache["write_pos"]
        pool_cache, keep_side = k_cache, None
        if index is not None:
            with jax.named_scope("attn.index"):
                plens = k_cache["prompt_lens"]
                keep = _index_keep(
                    cfg, index, _paged_index_keys(cfg, k_cache),
                    (plens + wp)[:, None],
                )[:, 0]
                t_pool = k_cache["table"].shape[1] * k_cache["pool"].shape[-2]
                t_side = k_cache["side"].shape[3]
                at = jnp.clip(
                    plens[:, None] + jnp.arange(t_side, dtype=jnp.int32)[None, :],
                    0, keep.shape[1] - 1,
                )
                keep_side = jnp.take_along_axis(keep, at, axis=1)
                pool_cache = {**k_cache, "keep": keep[:, :t_pool]}
        acc1, m1, l1 = decode_attention(
            q[:, 0], pool_cache, None, k_cache["prompt_lens"]
        )  # [B,1,H,rkv] / [B,1,H]
        with jax.named_scope("attn.kv_gather"):
            ks = layer_view(k_cache, "side", "side_layer")
            if ks.shape[-1] != row_w:
                ks = ks[..., :row_w]
        s2 = jnp.einsum("bkgd,bktd->bkgt", qg[:, 0], ks) * scale
        tpos = jnp.arange(ks.shape[2])
        seen = tpos[None, :] <= wp[:, None]
        if keep_side is not None:
            seen = seen & keep_side
        s2 = jnp.where(seen[:, None, None, :], s2, -jnp.inf)
        m2 = jnp.max(s2, axis=-1)  # finite: the current token is col wp
        p2 = jnp.exp(s2 - m2[..., None])
        l2 = jnp.sum(p2, axis=-1)
        acc2 = jnp.einsum("bkgt,bktd->bkgd", p2, ks[..., :rkv])
        m_t = jnp.maximum(m1, m2)
        w1 = jnp.exp(m1 - m_t)  # 0 for empty prompts (m1=-inf)
        w2 = jnp.exp(m2 - m_t)
        out = (acc1 * w1[..., None] + acc2 * w2[..., None]) / (
            l1 * w1 + l2 * w2
        )[..., None]
        return out.reshape(b, 1, hq, rkv)
    with jax.named_scope("attn.kv_gather"):
        if is_carry_cache(k_cache):
            kf = layer_view(k_cache, "all", "layer")
        else:
            kf = k_cache.astype(jnp.float32)
    keys = kf if kf.shape[-1] == row_w else kf[..., :row_w]
    scores = jnp.einsum("bskgd,bktd->bkgst", qg, keys) * scale
    kpos = jnp.arange(kf.shape[2])
    if jnp.ndim(offset) == 1:  # batched decode: one offset per row
        qpos = offset[:, None] + jnp.arange(s, dtype=jnp.int32)
        mask = kpos[None, None, :] <= qpos[:, :, None]
    else:
        mask = (kpos[None, :] <= offset + jnp.arange(s)[:, None])[None]
    if index is not None:
        with jax.named_scope("attn.index"):
            t = kf.shape[2]
            pad = (-t) % cfg.index_kpool
            ikeys = jnp.pad(_index_keys(cfg, kf[:, 0]), ((0, 0), (0, pad), (0, 0)))
            qpos = jnp.broadcast_to(
                jnp.reshape(jnp.asarray(offset, jnp.int32), (-1, 1))
                + jnp.arange(s, dtype=jnp.int32)[None, :],
                (b, s),
            )
            mask = mask & _index_keep(cfg, index, ikeys, qpos)[..., :t]
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bskgd", probs, kf[..., :rkv])
    return out.reshape(b, s, hq, rkv)


def _latent_attention_block(
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B,S,D]
    layer: Params,
    k_cache,  # rows [B,1,T,rkv+rope], or the paged / carry leaf of them
    v_cache,  # the zero-width twin the cache plumbing carries: never read
    offset: jnp.ndarray,
    cos: jnp.ndarray,  # [B,S,rope/2]
    sin: jnp.ndarray,
    decode_attention,
):
    """Latent attention, absorbed form, for prefill chunks and decode
    alike. The cache row of a token is ``[c_kv (after its norm and scale)
    | k_rope (after rope)]``; ``W_kvb``'s key half folds into the query
    (``q_lat``) and its value half into the output, so cached rows are
    never expanded to heads."""
    b, s, d = x.shape
    hq, rkv = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if is_quantized_cache(k_cache) or (
        isinstance(k_cache, dict)
        and any(isinstance(v, dict) for v in k_cache.values())
    ):
        raise ValueError("a latent cache is not quantized (kv_quantize)")
    f32 = jnp.float32
    with jax.named_scope("attn.norm_qkv"):
        c_q = rms_norm(dense_dot(x, layer["w_qa"]), layer["q_a_norm"], cfg.norm_eps)
        q = dense_dot(c_q, layer["w_qb"]).reshape(b, s, hq, nope + rope)
        if cfg.mla_scale_q_lora:
            q = q * math.sqrt(d / cfg.q_lora_rank)
        kv = dense_dot(x, layer["w_kva"])  # [B,S,rkv+rope]
        c_kv = rms_norm(kv[..., :rkv].astype(f32), layer["kv_a_norm"], cfg.norm_eps)
        if cfg.mla_scale_kv_lora:
            c_kv = c_kv * math.sqrt(d / rkv)
        # no rotary part (NoPE latent attention): nothing rotates
        k_rope = [apply_rope(kv[..., None, rkv:], cos, sin)] if rope else []  # one shared head
        parts = [c_kv.astype(x.dtype)[:, :, None, :]] + k_rope
    index = None
    if cfg.index_topk:
        with jax.named_scope("attn.index"):
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            index = (
                dense_dot(c_q, layer["idx_wq"]).reshape(b, s, hi, di).astype(f32),
                dense_dot(x, layer["idx_ww"]).astype(f32),
            )
            # the index key rides in the cached row, after its values
            parts.append(dense_dot(x, layer["idx_wk"])[:, :, None, :].astype(x.dtype))
    with jax.named_scope("attn.norm_qkv"):
        row = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        # [B,S,1,cache_k_width]
        q_rope = apply_rope(q[..., nope:], cos, sin) if rope else q[..., nope:]
        wk, sk, wv, sv = _kvb_parts(cfg, layer["w_kvb"], x.dtype)
        q_nope = q[..., :nope]
        if sk is not None:
            q_nope = (q_nope.astype(f32) * sk).astype(x.dtype)
        q_lat = jnp.einsum(
            "bshn,chn->bshc", q_nope, wk, preferred_element_type=f32
        )
        q_abs = jnp.concatenate([q_lat, q_rope.astype(f32)], axis=-1)
    with jax.named_scope("attn.kv_write"):
        k_cache, v_cache = _kv_write(k_cache, v_cache, row, row[..., :0], offset)
    with jax.named_scope("attn.core"):
        o_lat = _latent_attend(cfg, q_abs, k_cache, offset, decode_attention, index)
    with jax.named_scope("attn.out"):
        out = jnp.einsum(
            "bshc,chv->bshv", o_lat.astype(x.dtype), wv,
            preferred_element_type=f32,
        )
        if sv is not None:
            out = out * sv
        out = out.astype(x.dtype).reshape(b, s, hq * cfg.v_head_dim)
        return dense_dot(out, layer["wo"]), k_cache, v_cache


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B,S] int32
    offset: jnp.ndarray,  # scalar int32, or [B] int32 (single-token decode only)
    k_cache: jnp.ndarray,  # [L,B,Hkv,T,Dh]
    v_cache: jnp.ndarray,
    decode_attention: Optional[DecodeAttentionFn] = None,
    prefill_attention: Optional[PrefillAttentionFn] = None,
    token_mask: Optional[jnp.ndarray] = None,
    stats: Optional[Dict[str, Any]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the stack over S tokens starting at ``offset``.

    Returns (hidden [B,S,D], new_k_cache, new_v_cache). Logits are computed
    separately (``logits_for``) so prefill never materialises [B,S,vocab].

    A model with state-space layers (``cfg.state_layers``) takes and
    returns, in ``k_cache``'s place, the record ``{"kv": <the attention
    layers' K cache>, "ssm": <the recurrent state, models/ssm.py>}``
    (:func:`is_state_cache`): the state travels beside the cache through
    every path that carries one.

    ``token_mask`` ``[B,S]`` marks the tokens whose results are wanted: an
    expert layer routes only those (a batch's finished and padding rows
    then read no expert), and a state-space layer's state stands still at
    the others (a prefix a row: its real tokens first). ``stats``, a dict the caller owns, receives what
    the stack counted on the way, as traced values of the caller's own
    trace: ``stats["moe"]``, int32 ``[4]`` summed over the layers (pairs on
    held, identity and absent experts, held experts touched;
    :func:`_moe_parts`). A model without an expert layer leaves it empty.
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = embed_lookup(
            params["embed"], tokens, params["final_norm"].dtype
        )
        if cfg.gemma_norm:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype=x.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, dtype=x.dtype)

        # offset is a scalar (shared) or [B] (per-sequence, batched decode).
        off = jnp.reshape(jnp.asarray(offset, dtype=jnp.int32), (-1, 1))
        positions = off + jnp.arange(s, dtype=jnp.int32)[None, :]  # [1|B, S]
        positions = jnp.broadcast_to(positions, (b, s))
        if cfg.position_embedding == "rope":
            cos, sin = rope_angles(
                positions, cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling
            )
        else:  # no position embedding: nothing rotates
            cos = sin = None
        if cfg.residual_streams > 1:
            # the token's embedding on every stream (Hyper-Connections,
            # arXiv:2409.19606: copied in, summed out)
            x = jnp.broadcast_to(
                x[:, :, None, :], (b, s, cfg.residual_streams, cfg.d_model)
            )

    stacked = {k: v for k, v in params.items() if k not in NON_LAYER_LEAVES}

    x, new_k, new_v = run_blocks(
        stacked, cfg, x, offset, k_cache, v_cache, cos, sin,
        decode_attention, prefill_attention, token_mask, stats,
    )
    with jax.named_scope("head"):
        if cfg.residual_streams > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps, gemma_style=cfg.gemma_norm)
    return x, new_k, new_v


# the attention mixer's stacked leaves, as long as the attention layers
_ATTENTION_LEAVES = (
    "wq", "wk", "wv", "wo", "bq", "bk", "bv",
    "w_qa", "q_a_norm", "w_qb", "w_kva", "kv_a_norm", "w_kvb",
    "idx_wq", "idx_wk", "idx_ww",
)


class _Run(NamedTuple):
    """One run of ``ModelConfig.layer_runs`` as ``run_blocks`` scans it."""

    dense: bool  # the run's FFN is dense and nothing else
    first: Optional[int]  # its first layer; None: the model's one run
    count: Optional[int]
    scanned: Params  # leaves as long as the run: the scan's xs
    whole: Params  # leaves as long as the whole stack, read at first + i
    mixer: str = MIXER_ATTENTION
    kind_first: Optional[int] = None  # the run's first entry in ...
    kind_leaves: Params = {}  # ... the leaves, cache and state of its kind
    expert_first: int = 0  # ... and in the expert layer's leaves
    ffn_leaves: Params = {}  # a dense prefix's FFN or the shared expert ...
    ffn_first: int = 0  # ... read at entry ffn_first + i


def run_blocks(
    stacked: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # [B,S,D] embedded inputs
    offset: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L',B,Hkv,T,Dh] — L' may be a slice of the stack
    v_cache: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    decode_attention: Optional[DecodeAttentionFn] = None,
    prefill_attention: Optional[PrefillAttentionFn] = None,
    token_mask: Optional[jnp.ndarray] = None,
    stats: Optional[Dict[str, Any]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scan the transformer blocks in ``stacked`` over ``x``.

    Factored out of :func:`forward` so every execution mode — single-device
    prefill/decode, the TP path, and the pipeline-parallel stage slice
    (parallel/pp.py, where each stage holds L/S layers of the stack) — runs
    the *same* layer math; there is exactly one implementation to keep
    correct per architecture quirk (gemma norms, qwen2 biases, …).

    The stack is ``cfg.layer_runs``: runs of layers of one kind, each ONE
    scan of the one layer body (one run for every model but a dense prefix
    before expert layers, which has two). Around every sublayer sits the
    residual path: ``x + F(norm(x))`` on one stream, or the streams'
    Sinkhorn-projected mixing (``cfg.residual_streams`` > 1: ``x`` is
    ``[B,S,n,D]``, :func:`_hc_map`).

    One scanned layer is ``cfg.blocks_per_layer`` attention blocks, each
    followed by its FFN; block ``j``'s leaves are named ``<leaf>_<j>``
    (``init_params``). The caches carry one entry per BLOCK on their
    leading axis, ``[L * n, ...]`` in layer order (block ``j`` of layer
    ``i`` at ``i * n + j``): the scans below see them as ``[L, n, ...]``
    slices or index them by ``i * n + j``. Where the experts sit beside
    dense FFNs (``cfg.d_ff_expert``), the expert layer reads the first
    block's normed FFN input and its result joins the residual stream at
    the layer's end, a shortcut past the rest of the layer. The expert
    layer's leaves are not scanned (:func:`expert_layer_leaves`).
    """
    n_blk = cfg.blocks_per_layer
    kind = cfg.ffn_kind
    # the recurrent state of a model with state-space layers rides in with
    # the K cache (is_state_cache) and through every scan's carry below;
    # None, an empty pytree, for every other model
    state = None
    if is_state_cache(k_cache):
        k_cache, state = k_cache["kv"], k_cache["ssm"]
    rm = cfg.residual_multiplier
    experts = {k: stacked[k] for k in expert_layer_leaves(cfg)}
    stacked = {k: v for k, v in stacked.items() if k not in experts}
    n_stack = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def _block_view(layer, j):
        if n_blk == 1:
            return layer
        sfx = f"_{j}"
        return {
            name[: -len(sfx)]: leaf
            for name, leaf in layer.items()
            if name.endswith(sfx)
        }

    def _block_cache(cache, j):
        """Block ``j``'s cache out of a layer's."""
        if n_blk == 1:
            return cache
        if is_carry_cache(cache):
            return {"all": cache["all"], "layer": cache["layer"] * n_blk + j}
        if is_paged_cache(cache):
            if "side_layer" not in cache:
                raise ValueError(
                    "a layer of several attention blocks pages its cache "
                    "in the stacked-hybrid layout only"
                )
            if "layer" in cache:  # the stacked pool whole: the block's entry
                return {
                    **cache,
                    "layer": cache["layer"] * n_blk + j,
                    "side_layer": cache["side_layer"] * n_blk + j,
                }
            return {
                **cache,
                "pool": jax.tree_util.tree_map(lambda a: a[j], cache["pool"]),
                "side_layer": cache["side_layer"] * n_blk + j,
            }
        return jax.tree_util.tree_map(lambda a: a[j], cache)

    def _merge_block_cache(cache, new, j):
        """The layer's cache with block ``j``'s writes in it."""
        if n_blk == 1:
            return new
        if is_carry_cache(cache):
            return {"all": new["all"], "layer": cache["layer"]}
        if is_paged_cache(cache):
            return {**cache, "side": new["side"]}
        return jax.tree_util.tree_map(lambda a, u: a.at[j].set(u), cache, new)

    def _off_residual(x, lw, sub):
        """A sublayer's input off the residual state, and the map its
        result returns through: the state itself and nothing with one
        stream (the plain residual), else a mix of the streams and the
        map's write weights and mixing matrix (:func:`_hc_map`)."""
        if cfg.residual_streams == 1:
            return x, None
        h_pre, h_post, h_res = _hc_map(
            cfg, x, {k: lw[f"hc_{sub}_{k}"] for k in ("phi", "alpha", "bias")}
        )
        return _hc_read(x, h_pre), (h_post, h_res)

    def _onto_residual(x, back, y, scope):
        """The residual state with the sublayer's result ``y`` in it:
        ``x + y`` under the sublayer's own ``scope``, or the streams mixed
        and written (``hc.post``, a sibling of the sublayer's scopes)."""
        if back is None:
            with jax.named_scope(scope):
                if rm != 1.0:
                    # the sublayer's result times the residual multiplier
                    return (
                        x.astype(jnp.float32) + rm * y.astype(jnp.float32)
                    ).astype(x.dtype)
                return x + y
        return _hc_write(x, *back, y)

    def _layer_step(
        x, layer, kc, vc, li=None, dense=True, mixer=MIXER_ATTENTION, st=None,
        in_record=None,
    ):
        # ONE body for every layer of every model; ``dense`` says that the
        # layer belongs to a run whose FFN is dense and nothing else,
        # ``mixer`` what the run's mixer is (attention over ``kc`` / ``vc``,
        # or a recurrence over the layer's state ``st``: Mamba-2's, with
        # ``in_record`` over its entry of the record, ssm_mixer, or KDA's,
        # kda_mixer), ``li`` is the layer's index into the expert layer's
        # leaves. The scope names are what a device trace is reduced by
        # (PERF.md §3): attn.norm_qkv / kv_write / kv_gather / core / out /
        # index inside _attention_block, ssm.* inside ssm_mixer, kda.*
        # inside kda_mixer, mlp here, moe.* inside _moe_parts, hc.* around
        # each sublayer of a model with several residual streams
        shortcut = counts = None
        for j in range(n_blk):
            lw = _block_view(layer, j)
            u, back = _off_residual(x, lw, "attn")
            with jax.named_scope(
                "ssm.in_proj" if mixer == MIXER_SSM
                else "kda.in_proj" if mixer == MIXER_KDA
                else "attn.norm_qkv"
            ):
                h = rms_norm(u, lw["attn_norm"], cfg.norm_eps, gemma_style=cfg.gemma_norm)
            if mixer == MIXER_SSM:
                mix_out, st = ssm_mixer(cfg, h, lw, st, token_mask, in_record)
                x = _onto_residual(x, back, mix_out, "ssm.out_proj")
            elif mixer == MIXER_KDA:
                mix_out, st = kda_mixer(cfg, h, lw, st, token_mask, in_record)
                x = _onto_residual(x, back, mix_out, "kda.out_proj")
            else:
                attn_out, kc_j, vc_j = _attention_block(
                    cfg, h, lw, _block_cache(kc, j), _block_cache(vc, j),
                    offset, cos, sin, decode_attention, prefill_attention,
                )
                kc = _merge_block_cache(kc, kc_j, j)
                vc = _merge_block_cache(vc, vc_j, j)
                x = _onto_residual(x, back, attn_out, "attn.out")
            u, back = _off_residual(x, lw, "mlp")
            with jax.named_scope("mlp"):
                h = rms_norm(u, lw["mlp_norm"], cfg.norm_eps, gemma_style=cfg.gemma_norm)
                if dense or kind == FFN_EXPERTS_BESIDE_DENSE:
                    mlp_out = _gated_ffn(cfg, h, lw["w_gate"], lw["w_up"], lw["w_down"])
                elif kind == FFN_EXPERTS:
                    mlp_out, counts = _moe_mlp(cfg, h, experts, li, token_mask)
                elif cfg.n_shared_experts:
                    # the shared expert: a dense FFN every token takes
                    mlp_out = _gated_ffn(cfg, h, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
                else:
                    mlp_out = None
            if kind == FFN_DENSE_THEN_EXPERTS and not dense:
                # the routed experts INSTEAD of a dense FFN, a sibling of
                # ``mlp`` in the trace (moe.* scopes)
                routed, counts = _moe_mlp(cfg, h, experts, li, token_mask)
                if mlp_out is None:
                    mlp_out = routed
                else:
                    with jax.named_scope("moe.combine"):
                        mlp_out = mlp_out + routed
            x_out = _onto_residual(x, back, mlp_out, "mlp")
            if kind == FFN_EXPERTS_BESIDE_DENSE and j == 0:
                shortcut, counts = _moe_mlp(cfg, h, experts, li, token_mask)
            x = x_out
        if shortcut is not None:
            x = _onto_residual(x, None, shortcut, "moe.combine")
        return x, kc, vc, counts, st

    def _per_layer(cache):
        """``[L * n, ...]`` cache leaves as ``[L, n, ...]`` scan slices."""
        if n_blk == 1:
            return cache
        return jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] // n_blk, n_blk, *a.shape[1:]), cache
        )

    def _per_block(cache):
        if n_blk == 1:
            return cache
        return jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] * n_blk, *a.shape[2:]), cache
        )

    # The stack as runs of layers of one kind, each ONE scan of
    # ``_layer_step``. A model of one run (every model but a dense prefix
    # before expert layers, and a stack of several mixers) scans all its
    # stacked leaves, and its caches, as xs: ``first`` is None. A model of
    # more keeps one set of leaves as long as the WHOLE stack (norms,
    # residual maps, and where every layer is attention its leaves) beside
    # the leaves of each run's own kind. A dense prefix's FFN and the later
    # layers' shared expert are as long as their ONE run, which scans them;
    # a mixer's leaves are as long as the layers of its kind, which several
    # runs share. Whatever a run does not scan it reads where it lies: layer
    # ``first + i`` of the whole stack's leaves and, of its own kind's
    # leaves, cache entries and state, entry ``kind_first + i`` (what a scan
    # does with its xs; a static slice of them would be a copy of the run's
    # weights every step). ``expert_first + i`` is the layer's entry in the
    # expert layer's leaves.
    if cfg.state_layers:
        recurrent = cfg.state_mixer
        by_mixer = {
            recurrent: {
                k: stacked[k]
                for k in (KDA_LEAVES if recurrent == MIXER_KDA else SSM_LEAVES)
            },
            MIXER_ATTENTION: {
                k: v for k, v in stacked.items() if k in _ATTENTION_LEAVES
            },
        }
        # a dense prefix's FFN and the later layers' shared expert are as
        # long as the layers of their kind (none without a dense prefix)
        lead = cfg.n_dense_layers
        by_ffn = {
            dense: {k: stacked[k] for k in names if k in stacked} if lead else {}
            for dense, names in ((True, DENSE_FFN_LEAVES), (False, SHARED_EXPERT_LEAVES))
        }
        whole = {
            k: v for k, v in stacked.items()
            if not any(k in part for part in (*by_mixer.values(), *by_ffn.values()))
        }
        runs = [
            _Run(
                dense, first, count, {}, whole, cfg.mixer_kind(first),
                cfg.kind_index(first), by_mixer[cfg.mixer_kind(first)],
                first - lead if lead and not dense else first,
                by_ffn[dense], 0 if dense else -lead,
            )
            for dense, first, count in cfg.layer_runs
        ]
    elif len(cfg.layer_runs) == 1:
        runs = [_Run(cfg.layer_runs[0][0], None, None, stacked, {})]
    else:
        short = DENSE_FFN_LEAVES + SHARED_EXPERT_LEAVES
        whole = {k: v for k, v in stacked.items() if k not in short}
        runs = [
            _Run(
                dense, first, count,
                {
                    k: stacked[k]
                    for k in (DENSE_FFN_LEAVES if dense else SHARED_EXPERT_LEAVES)
                    if k in stacked
                },
                whole, MIXER_ATTENTION, first,
            )
            for dense, first, count in cfg.layer_runs
        ]

    def _layer_at(run, layer, li):
        """The run's layer ``li``: its scanned leaves with the whole
        stack's leaves of layer ``first + li`` and its own kind's of entry
        ``kind_first + li``; and that entry's number."""
        if run.first is None:
            return layer, li
        layer = {**layer, **_layer_of(run.whole, run.first + li)}
        if run.kind_leaves:
            layer.update(_layer_of(run.kind_leaves, run.kind_first + li))
        if run.ffn_leaves:
            layer.update(_layer_of(run.ffn_leaves, run.ffn_first + run.first + li))
        return layer, run.kind_first + li

    def _expert_at(run, li):
        """Layer ``li`` of the run's entry in the expert layer's leaves."""
        return li + run.expert_first if run.expert_first else li

    # what a state-space layer does with the record's ``s`` (the ONE rule,
    # ssm_step_impl): a one-token step hands the mixer the record WHOLE and
    # takes it back, its entry updated for the live rows where it lies (a
    # slice handed to the kernel would be a copy of a layer's state in and
    # out); what an attention layer's paged decode does with the side
    # caches (the ONE rule, side_impl): the live rows' written columns read
    # where they lie. The rows, compacted once for all the layers of both
    state_live = state is not None and (
        state_step_impl(cfg, state, x.shape[1]) == "pallas-live"
    )
    # the scope a state-space layer's own reads and writes of the record
    # run under: its mixer's update
    update_scope = "kda.update" if cfg.state_mixer == MIXER_KDA else "ssm.update"
    side_live = (
        is_paged_cache(k_cache)
        and "side" in k_cache
        and side_impl(cfg, k_cache["side"], x.shape[1]) == "pallas-live"
    )
    live = None
    if state_live or side_live:
        with jax.named_scope(update_scope if state_live else "attn.kv_gather"):
            live = live_rows(
                None if token_mask is None else token_mask[:, 0], x.shape[0]
            )

    def _state_step(run, state, x, layer, li, at):
        """A state-space layer of ``run``: its state read at entry ``at``
        of the record, and written back there where it lies -- ``s`` by
        the step's kernel for the live rows (``live``), or like the
        convolution's tail sliced out for the whole bucket, with the
        layer's update fused into the write."""
        sliced = state if not state_live else {"conv": state["conv"]}
        with jax.named_scope(update_scope):
            st = _layer_of(sliced, at)
        in_record = None
        if state_live:
            st, in_record = {**st, "s": state["s"]}, (at, *live)
        x, _, _, counts, st = _layer_step(
            x, layer, None, None, _expert_at(run, li), run.dense, run.mixer, st,
            in_record,
        )
        with jax.named_scope(update_scope):
            # the layer's update fuses into this write: its device time
            # is the write's operation's
            state = {
                k: st[k]
                if k not in sliced
                else jax.lax.dynamic_update_index_in_dim(
                    a, st[k].astype(a.dtype), at, 0
                )
                for k, a in state.items()
            }
        return x, state, counts

    all_counts = []

    def _keep(counts):
        if counts is not None:
            all_counts.append(jnp.sum(counts, axis=0))

    if is_paged_cache(k_cache) and "side" in k_cache:
        # STACKED-HYBRID paged mode: the [L,P,Hkv,page,Dp] pools are
        # READ-ONLY during decode (they hold only prefill pages, rebuilt
        # per batch call) and stream through scan xs WITHOUT ys — XLA
        # pipelines the per-layer slices like the weights, with no
        # copy-back and no dynamic layer indexing. Only the small
        # contiguous side caches ([L,B,Hkv,Tgen,D], this call's
        # generated tokens) ride xs AND ys. The rejected write designs
        # each measured a full-pool copy on real hardware: pool-as-ys
        # copies once per STEP (~3× slower than contiguous batched
        # decode), pool-as-carry with an in-scan traced-layer scatter
        # copies once per LAYER (~52 ms/step), a single deferred batched
        # scatter per step still staged both pools (~+7.6 ms/step) —
        # docs/PERF.md. The legacy xs/ys mode below survives for paths
        # without the parts kernel (multi-device meshes use the gather
        # fallback).
        table = k_cache["table"]
        wp = k_cache["write_pos"]
        plens = k_cache["prompt_lens"]
        owners = (
            {"owners": k_cache["owners"]} if "owners" in k_cache else {}
        )
        # the held-page kernel (the engine's ``pallas-pool``: ``owners``
        # carries the held pages' list) reads the stacked pool WHOLE at
        # the layer's entry: a scanned slice handed to the kernel would be
        # a copy of the layer's pool
        pool_whole = bool(owners) and owners["owners"].order is not None
        side_rows = {"live": live} if side_live else {}
        # pools ride scan xs WITHOUT ys: read-only per-layer slices that
        # XLA streams/pipelines like the weights — no copy-back, and no
        # traced-layer dynamic indexing to defeat the scan's schedule.
        # The SIDE caches ride the CARRY as the whole [L,B,Hkv,Tgen,D]
        # stack with per-layer in-place token writes (side_layer) — as
        # xs AND ys, XLA wrote back the full per-layer side every layer
        # (1.5 ms/step at 128 rows, docs/paged_trace_128rows.json), the
        # same copy tax the contiguous path's carry-resident cache
        # removed. The recurrent state rides the carry the same way.
        pool_codes = (
            k_cache["pool"]["q"]
            if isinstance(k_cache["pool"], dict)
            else k_cache["pool"]
        )
        side_k, side_v = k_cache["side"], v_cache["side"]
        for run in runs:

            def block_paged(carry, xs, run=run):
                x, ks_all, vs_all, state = carry
                if run.first is None and not pool_whole:
                    layer, kp_l, vp_l, li = xs
                    at = li
                else:
                    layer, li = xs
                    layer, at = _layer_at(run, layer, li)
                    if run.mixer in STATE_MIXERS:
                        x, state, counts = _state_step(run, state, x, layer, li, at)
                        return (x, ks_all, vs_all, state), counts
                    if pool_whole:
                        kp_l, vp_l = k_cache["pool"], v_cache["pool"]
                    else:
                        kp_l = _layer_of(k_cache["pool"], at)
                        vp_l = _layer_of(v_cache["pool"], at)
                whole = {"layer": at} if pool_whole else {}
                kc = {
                    "pool": kp_l, "table": table,
                    "side": ks_all, "side_layer": at,
                    "write_pos": wp, "prompt_lens": plens, **owners,
                    **side_rows, **whole,
                }
                vc = {
                    "pool": vp_l, "table": table,
                    "side": vs_all, "side_layer": at,
                    "write_pos": wp, "prompt_lens": plens, **whole,
                }
                x, kc, vc, counts, _ = _layer_step(
                    x, layer, kc, vc, _expert_at(run, li), run.dense
                )
                return (x, kc["side"], vc["side"], state), counts

            (x, side_k, side_v, state), counts = jax.lax.scan(
                block_paged,
                (x, side_k, side_v, state),
                (
                    run.scanned,
                    _per_layer(k_cache["pool"]),
                    _per_layer(v_cache["pool"]),
                    jnp.arange(pool_codes.shape[0] // n_blk),
                )
                if run.first is None and not pool_whole
                else (
                    run.scanned,
                    jnp.arange(
                        pool_codes.shape[0] // n_blk
                        if run.first is None
                        else run.count
                    ),
                ),
            )
            _keep(counts)
        new_k = {**k_cache, "side": side_k}
        new_v = {**v_cache, "side": side_v}
    elif (
        (isinstance(k_cache, jnp.ndarray) or is_quantized_cache(k_cache))
        and jnp.ndim(offset) == 1
    ):
        # Batched per-row-offset decode over stacked caches (plain
        # arrays or int8-KV {"q","s"} dicts) — single-token steps and
        # the speculative verify's k+1-token blocks alike: the caches
        # ride the scan CARRY
        # and each layer writes only its token's row in place
        # (is_carry_cache). Scanning them as xs AND ys instead makes
        # XLA write back the full per-layer cache every layer —
        # 1.4 GB/step of copy for a 64 KB update at 128 rows, the
        # dominant wide-batch cost (docs/paged_trace_128rows.json).
        # The per-layer read is unchanged either way: attention
        # consumes the whole slice.
        n_layers = (
            k_cache["q"] if isinstance(k_cache, dict) else k_cache
        ).shape[0]
        new_k, new_v = k_cache, v_cache
        for run in runs:

            def block_carry(carry, xs, run=run):
                x, kc_all, vc_all, state = carry
                layer, li = xs
                layer, at = _layer_at(run, layer, li)
                if run.mixer in STATE_MIXERS:
                    x, state, counts = _state_step(run, state, x, layer, li, at)
                    return (x, kc_all, vc_all, state), counts
                x, kc, vc, counts, _ = _layer_step(
                    x,
                    layer,
                    {"all": kc_all, "layer": at},
                    {"all": vc_all, "layer": at},
                    _expert_at(run, li),
                    run.dense,
                )
                return (x, kc["all"], vc["all"], state), counts

            (x, new_k, new_v, state), counts = jax.lax.scan(
                block_carry,
                (x, new_k, new_v, state),
                (
                    run.scanned,
                    jnp.arange(
                        n_layers // n_blk if run.first is None else run.count
                    ),
                ),
            )
            _keep(counts)
    else:
        new_k, new_v = k_cache, v_cache
        for run in runs:

            def block(carry, xs, run=run):
                x, state = carry
                layer, kc, vc, *li = xs
                if run.first is not None:
                    layer, at = _layer_at(run, layer, li[0])
                    if run.mixer in STATE_MIXERS:
                        x, state, counts = _state_step(
                            run, state, x, layer, li[0], at
                        )
                        return (x, state), (kc, vc, counts)
                x, kc, vc, counts, _ = _layer_step(
                    x, layer, kc, vc, *(_expert_at(run, i) for i in li),
                    dense=run.dense,
                )
                return (x, state), (kc, vc, counts)

            if run.first is None:
                (x, state), (new_k, new_v, counts) = jax.lax.scan(
                    block,
                    (x, state),
                    (run.scanned, _per_layer(k_cache), _per_layer(v_cache))
                    + ((jnp.arange(n_stack),) if experts else ()),
                )
                new_k, new_v = _per_block(new_k), _per_block(new_v)
            elif run.mixer in STATE_MIXERS:
                (x, state), (_, _, counts) = jax.lax.scan(
                    block, (x, state), (run.scanned, None, None, jnp.arange(run.count)),
                )
            else:
                # the run's own entries of the cache, scanned and put back
                lo, hi = run.kind_first, run.kind_first + run.count

                def part(cache, lo=lo, hi=hi):
                    return jax.tree_util.tree_map(lambda a: a[lo:hi], cache)

                (x, state), (run_k, run_v, counts) = jax.lax.scan(
                    block, (x, state),
                    (run.scanned, part(k_cache), part(v_cache), jnp.arange(run.count)),
                )
                new_k, new_v = (
                    jax.tree_util.tree_map(
                        lambda a, u, lo=lo, hi=hi: a.at[lo:hi].set(u), cache, new
                    )
                    for cache, new in ((new_k, run_k), (new_v, run_v))
                )
            _keep(counts)
    if state is not None:
        new_k = {"kv": new_k, "ssm": state}
    if stats is not None and all_counts:
        stats["moe"] = functools.reduce(jnp.add, all_counts)
    return x, new_k, new_v


def logits_for(params: Params, cfg: ModelConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Project hidden states [..., D] to vocab logits in float32.

    Quantized heads dequantize to bf16 operands with f32 MXU accumulation:
    an f32 dequant of a 150k-vocab table is a multi-GB temporary that can
    decide whether an 8B model fits the chip at all; full-precision heads
    keep the all-f32 path (the HF parity tests pin its numerics)."""
    leaf = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    pattern = "...d,vd->...v" if cfg.tie_embeddings else "...d,dv->...v"
    with jax.named_scope("head"):
        if is_quantized(leaf):
            head = maybe_dequant(leaf, jnp.bfloat16)
            logits = jnp.einsum(
                pattern,
                hidden.astype(jnp.bfloat16),
                head,
                preferred_element_type=jnp.float32,
            )
        else:
            logits = jnp.einsum(
                pattern, hidden.astype(jnp.float32), leaf.astype(jnp.float32)
            )
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


@dataclasses.dataclass
class Transformer:
    """Config + params bundle with convenience entry points."""

    cfg: ModelConfig
    params: Params

    @classmethod
    def initialise(
        cls, cfg: ModelConfig, seed: int = 0, dtype: jnp.dtype = jnp.bfloat16
    ) -> "Transformer":
        return cls(cfg=cfg, params=init_params(cfg, jax.random.PRNGKey(seed), dtype))

    def init_cache(
        self, batch: int, max_len: int, dtype: jnp.dtype = jnp.bfloat16
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The contiguous cache pair ``[L', B, heads, T, width]``, sized
        by the config's cache properties: K and V heads, or a latent
        cache's one row a token and block in the K leaf beside a
        zero-width V leaf (the plumbing carries the pair; nothing reads
        or stores the second)."""
        cfg = self.cfg
        lead = (cfg.cache_layers, batch, cfg.cache_heads, max_len)
        k_cache = jnp.zeros(lead + (cfg.cache_k_width,), dtype=dtype)
        if cfg.state_layers:
            # the recurrent state rides beside the K cache (is_state_cache)
            k_cache = {"kv": k_cache, "ssm": init_state(cfg, batch, dtype)}
        return k_cache, jnp.zeros(lead + (cfg.cache_v_width,), dtype=dtype)

    def __call__(self, tokens, offset, k_cache, v_cache, decode_attention=None):
        return forward(
            self.params, self.cfg, tokens, offset, k_cache, v_cache, decode_attention
        )
