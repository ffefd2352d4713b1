"""Sharding rules for the transformer parameter/cache pytrees.

Megatron-style tensor parallelism expressed as GSPMD placement: annotate the
weights with NamedSharding over the ``tp`` axis and jit the *unchanged*
forward function — XLA inserts the all-gather/reduce-scatter collectives over
ICI (the scaling-book recipe: pick a mesh, annotate, let XLA do the rest).

Layout (param leaves carry a leading stacked-layer axis L):
  wq/wk/wv  [L, D, H·Dh]   → shard the head (output) dim over tp
  wo        [L, H·Dh, D]   → shard the head (input) dim over tp  (psum after)
  w_gate/up [L, D, F]      → shard F over tp
  w_down    [L, F, D]      → shard F over tp                      (psum after)
  embed     [V, D]         → shard V over tp (logits gather over vocab shards)
  KV cache  [L, B, Hkv, T, Dh] → shard Hkv over tp when divisible, else
                                  replicate (MQA/small-GQA caches are tiny)
  norms / biases           → replicated
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig, UnsupportedMechanism


def _tp_size(mesh: Mesh) -> int:
    return mesh.shape.get("tp", 1)


def param_specs(cfg: ModelConfig, mesh: Mesh) -> Dict[str, P]:
    """PartitionSpec per parameter leaf (leading axis L is never sharded)."""
    if (
        cfg.latent
        or cfg.blocks_per_layer > 1
        or cfg.residual_streams > 1
        or len(cfg.layer_runs) > 1
    ):
        raise UnsupportedMechanism(
            "mesh", cfg.name,
            "latent attention, layers of several attention blocks, several "
            "residual streams and a stack of more than one run of layers "
            "have no partition rules (parallel/*); they run on one device",
        )
    tp = _tp_size(mesh)
    ep = mesh.shape.get("ep", 1)

    def div(n: int) -> bool:
        return tp > 1 and n % tp == 0

    # Expert axis over ep: each device holds E/ep whole experts. The
    # expert layer reads a block's expert by index (models/transformer.py
    # _moe_parts), so GSPMD fetches it to every device; placement saves
    # the bytes at rest, an expert-parallel exchange is not built.
    e_ax = "ep" if cfg.n_experts and ep > 1 and cfg.n_experts % ep == 0 else None
    f_ax = "tp" if div(cfg.d_ff) else None

    specs: Dict[str, P] = {
        "embed": P("tp", None) if div(cfg.vocab_size) else P(),
        "attn_norm": P(),
        "mlp_norm": P(),
        "final_norm": P(),
        "wq": P(None, None, "tp") if div(cfg.n_heads * cfg.d_head) else P(),
        "wk": P(None, None, "tp") if div(cfg.n_kv_heads * cfg.d_head) else P(),
        "wv": P(None, None, "tp") if div(cfg.n_kv_heads * cfg.d_head) else P(),
        "wo": P(None, "tp", None) if div(cfg.n_heads * cfg.d_head) else P(),
    }
    if cfg.n_experts:
        specs.update(
            router=P(),
            w_gate=P(None, e_ax, None, f_ax),
            w_up=P(None, e_ax, None, f_ax),
            w_down=P(None, e_ax, f_ax, None),
        )
    else:
        specs.update(
            w_gate=P(None, None, f_ax),
            w_up=P(None, None, f_ax),
            w_down=P(None, f_ax, None),
        )
    if cfg.qkv_bias:
        specs["bq"] = P(None, "tp") if div(cfg.n_heads * cfg.d_head) else P()
        specs["bk"] = P(None, "tp") if div(cfg.n_kv_heads * cfg.d_head) else P()
        specs["bv"] = P(None, "tp") if div(cfg.n_kv_heads * cfg.d_head) else P()
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp") if div(cfg.vocab_size) else P()
    return specs


def param_shardings(
    cfg: ModelConfig, mesh: Mesh
) -> Dict[str, NamedSharding]:
    return {
        name: NamedSharding(mesh, spec)
        for name, spec in param_specs(cfg, mesh).items()
    }


def cache_spec(cfg: ModelConfig, mesh: Mesh, batch_axis: str | None = None) -> P:
    """KV cache [L, B, Hkv, T, Dh]: heads over tp, optionally batch over dp."""
    tp = _tp_size(mesh)
    head_axis = "tp" if tp > 1 and cfg.n_kv_heads % tp == 0 else None
    return P(None, batch_axis, head_axis, None, None)


def cache_shardings(
    cfg: ModelConfig, mesh: Mesh, batch_axis: str | None = None
) -> NamedSharding:
    return NamedSharding(mesh, cache_spec(cfg, mesh, batch_axis))


def quant_cache_shardings(
    cfg: ModelConfig, mesh: Mesh, batch_axis: str | None = None
) -> Dict[str, NamedSharding]:
    """Shardings for an int8-quantized cache leaf ``{"q", "s"}``
    (models/quantize.py): codes ``q`` [L,B,Hkv,T,Dh] take the bf16 cache's
    spec; scales ``s`` [L,B,Hkv,T] take the same spec minus the head dim
    it reduced away."""
    spec = cache_spec(cfg, mesh, batch_axis)
    return {
        "q": NamedSharding(mesh, spec),
        "s": NamedSharding(mesh, P(*tuple(spec)[:-1])),
    }


def stepped_carry_shardings(
    cfg: ModelConfig,
    mesh: Mesh,
    carry: Dict[str, Any],
    draft_cfg: "ModelConfig | None" = None,
) -> Dict[str, Any]:
    """NamedSharding pytree for a stepped-decode session carry
    (engine/stepped.py): the per-iteration SPMD placement that makes the
    continuous scheduler device-count-agnostic.

    One rule per carry leaf, mirroring the monolithic paths' placements
    so the jitted slice step neither reshards nor bounces through host:

    - KV payload shards over the heads axis when ``n_kv_heads`` divides
      ``tp`` (the ONE divisibility rule, ``cache_spec``): the contiguous
      batch cache ``k_cache``/``v_cache`` [L,B,Hkv,T,Dh], the page pool
      ``pool_k``/``pool_v`` [L,P,Hkv,page,D] (pages sit in the
      batch-like position), the stacked side caches
      ``side_k``/``side_v`` [L,B,Hkv,Tgen,D], and a kernel-less
      speculative session's native-verify scratch
      ``scratch_k``/``scratch_v`` [L,B,Hkv,k+1,Dh] (ISSUE 10 — a mini
      contiguous cache holding one round's candidate K/V, so the same
      head rule applies verbatim). Int8 ``{"q","s"}`` leaves
      place codes with the payload spec and the per-position scales with
      the head-reduced spec (``quant_cache_shardings`` applied
      leaf-wise).
    - A speculative session's DRAFT cache (``draft_k``/``draft_v`` —
      engine/speculative.py's batched step) is a contiguous batch cache
      of the DRAFT model, so it takes ``cache_spec(draft_cfg)``: sharded
      over the draft's own heads when THEY divide ``tp``, replicated
      otherwise (a draft whose heads don't divide the mesh is tiny by
      construction — replication is the honest placement). The draft
      cache is never quantized.
    - Everything row-control — tokens, offsets, prompt_lens, remaining,
      done, rngs, presence, sampling knobs, the page table, and the
      speculative per-row state (``draft_offsets``, ``spec_rounds``,
      ``spec_accepted``, ``spec_drafted``, ``spec_rejected``, and the
      n-gram draft source's token history ``ngram_hist``/``ngram_len``
      — ISSUE 16) — replicates (tiny per-row metadata every device
      reads each step; the host mutates it between slices with O(B)
      scatters).

    When the mesh carries a ``dp`` axis (``MeshSpec.dp_tp`` — ISSUE 19's
    tp×dp in-mesh row sharding), the ROW dimension additionally shards
    over ``dp`` under the same divisibility discipline as the head rule:

    - batch-position payload leaves (``k_cache``/``v_cache``,
      ``side_k``/``side_v``, ``scratch_k``/``scratch_v``, the draft
      cache) take ``cache_spec(batch_axis="dp")`` when the bucket width
      B divides ``dp``;
    - the page pool shards its page dim over ``dp`` when the page count
      divides ``dp`` (pages are pre-partitioned into per-shard ranges by
      ``PagePool.dp_shards`` so a row's pages live on the shard that
      owns the row — best-effort locality; correctness never depends on
      it because GSPMD treats the table gather globally);
    - row-control leaves with a leading row dim B (tokens, offsets,
      done, rngs, the page table, spec counters, n-gram history, …)
      shard that dim over ``dp`` instead of replicating.

    Any leaf that fails its divisibility check falls back to the tp-only
    placement above — the exact analogue of the heads∤tp replicate rule,
    so a dp mesh is always safe to request.
    """
    dp = mesh.shape.get("dp", 1)
    tok = carry.get("tokens")
    b = int(tok.shape[0]) if tok is not None and getattr(tok, "ndim", 0) else 0
    row_shard = dp > 1 and b > 0 and b % dp == 0
    batch_axis = "dp" if row_shard else None

    spec = cache_spec(cfg, mesh, batch_axis)
    payload = NamedSharding(mesh, spec)
    scale = NamedSharding(mesh, P(*tuple(spec)[:-1]))
    repl = NamedSharding(mesh, P())
    pool_keys = ("pool_k", "pool_v")
    payload_keys = (
        "k_cache", "v_cache", "pool_k", "pool_v",
        "side_k", "side_v", "scratch_k", "scratch_v",
    )
    draft_payload = NamedSharding(
        mesh,
        cache_spec(draft_cfg if draft_cfg is not None else cfg, mesh, batch_axis),
    )
    head_axis = tuple(cache_spec(cfg, mesh))[2]

    def pool_place(leaf):
        # Pool [L, P, Hkv, page, D]: the page dim sits in the batch-like
        # position, but its extent is the page count, not B — check its
        # own divisibility before engaging dp.
        q = leaf["q"] if isinstance(leaf, dict) else leaf
        n_pages = int(q.shape[1])
        ax = "dp" if row_shard and n_pages % dp == 0 else None
        pspec = P(None, ax, head_axis, None, None)
        if isinstance(leaf, dict):
            return {
                "q": NamedSharding(mesh, pspec),
                "s": NamedSharding(mesh, P(*tuple(pspec)[:-1])),
            }
        return NamedSharding(mesh, pspec)

    def row_place(leaf):
        # Row-control leaf [B, ...]: shard the row dim, replicate the rest.
        nd = getattr(leaf, "ndim", 0)
        return NamedSharding(mesh, P(*(("dp",) + (None,) * (nd - 1))))

    def place(key: str, leaf):
        if key in ("draft_k", "draft_v"):
            return draft_payload
        if key in pool_keys and getattr(leaf, "ndim", 1) != 0:
            if isinstance(leaf, dict) or getattr(leaf, "ndim", 0) == 5:
                return pool_place(leaf)
        if key not in payload_keys:
            if (
                row_shard
                and not isinstance(leaf, dict)
                and getattr(leaf, "ndim", 0) >= 1
                and int(leaf.shape[0]) == b
            ):
                return row_place(leaf)
            return repl
        if isinstance(leaf, dict):  # int8: codes + per-position scales
            return {"q": payload, "s": scale}
        if getattr(leaf, "ndim", 0) == 0:
            return repl  # legacy-mode side-cache sentinel (scalar 0)
        return payload

    return {key: place(key, leaf) for key, leaf in carry.items()}


def shard_model(params: Dict[str, Any], cfg: ModelConfig, mesh: Mesh) -> Dict[str, Any]:
    """Place an existing params pytree onto the mesh per the TP rules.

    Int8-quantized leaves (``{"q", "s"}``, see models/quantize.py) shard the
    int8 tensor with the weight's spec; the per-channel scale has size 1 on
    the reduced input axis (-2), so that axis's sharding is dropped for it.
    """
    from ..models.quantize import is_quantized

    specs = param_specs(cfg, mesh)
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        spec = specs[name]
        if is_quantized(leaf):
            # int8 stores "q" [..., in, out]; int4 stores "q4" (input axis
            # packed to in/2), int4-i32 stores "q32" (in/8) — the same spec
            # applies (axis order is unchanged; dividing the input dim
            # preserves divisibility for the even tp sizes the sharder
            # accepts).
            qkey = next(k for k in ("q4", "q32", "q") if k in leaf)
            parts = list(spec) + [None] * (leaf[qkey].ndim - len(spec))
            # The scale has size 1 on whichever axis was reduced (the input
            # axis for matmul weights, the feature axis for row-wise
            # embedding scales) — drop that axis's sharding for it.
            scale_parts = [
                p if dim != 1 else None
                for p, dim in zip(parts, leaf["s"].shape)
            ]
            out[name] = {
                qkey: jax.device_put(
                    leaf[qkey], NamedSharding(mesh, P(*parts))
                ),
                "s": jax.device_put(
                    leaf["s"], NamedSharding(mesh, P(*scale_parts))
                ),
            }
        else:
            out[name] = jax.device_put(leaf, NamedSharding(mesh, spec))
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
