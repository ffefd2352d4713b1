"""The GLM-5.3-Flash family (``benchmark/configs/glm53-flash-ep8-pp5.json``
states the deployment and what of the model this chip holds): a stack of
Kimi Delta Attention layers (linear attention with a per-row recurrent
state) among sparse latent attention layers behind a lightning indexer,
four residual streams, dense leading layers before expert layers.

Plain reference of the served model, written layer by layer in a Python
loop, float32, ``default_matmul_precision("highest")``, one sublayer
dequantized at a time: the residual streams as an explicit ``[T, n, d]``
array, KDA as its recurrence scanned over TOKENS in the matrix form below
(no chunked form, no state handed in, every request from position 0),
latent attention EXPANDED to its heads, and the indexer scored over every
block of every key before its top-k is taken. It imports nothing of the
program.

``d`` = ``hidden_size``; RMSNorm ``eps`` = ``rms_norm_eps`` with every gain 1
(the recipe's), so the gains are left out. The chip holds the first
``num_hidden_layers`` layers of the published lists (``layer_types``,
``mlp_layer_types``). **The residual path** is xing4's (``families/xing4.py``:
mHC, arXiv:2512.24880, the mixing logits clamped at +-``hc_res_clamp``):
``X_0[i] = embed(token)``, every sublayer ``F`` reads ``u = sum_i Hp[i] X[i]``
and writes ``X'[i] = sum_j M[i, j] X[j] + Hq[i] F(norm(u))``; the head reads
``norm(sum_i X[i]) W_head``.

**KDA** (a ``linear_attention`` layer; ``H``, ``K`` = ``linear_attn_config``'s
``num_heads``, ``head_dim``; ``L`` = ``gate_lower_bound``; a conv
``short_conv_kernel_size`` wide)::

    [qkv | b | fa | ga] = u W_in;  f = fa W_fb;  gate = ga W_gb
    q, k, v = silu(sum_j w_conv[j] * qkv_{t-3+j})          # causal, zeros before the sequence
    q = q / |q| / sqrt(K);  k = k / |k|                     # a head's K values, |x| = sqrt(sum x^2 + 1e-6)
    beta = sigmoid(b);  g = L sigmoid(exp(A_log) (f + dt_bias))
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,   S_{-1} = 0
    o_t = S_t^T q_t;  y = (norm(o_t) * sigmoid(gate)) W_out  # norm over a head's values

**DSA** (a ``deepseek_sparse_attention`` layer: latent attention with no
rotary part, ``mla_use_nope``)::

    c_q = norm(u W_qa);  q = (c_q W_qb) as [H, nope];  c_kv = norm(u W_kva)
    [k | v] = (c_kv W_kvb) as [H, nope + v];  score[t, s] = q_t . k_s / sqrt(nope)
    Iq = (c_q W_Iq) as [Hi, Di];  Ik = u W_Ik;  Iw = u W_Iw                   # the lightning indexer
    P_n = mean(Ik_{4n .. 4n+3});  I[t, n] = sum_j Iw[t, j] relu(Iq[t, j] . P_n)
    keep(t) = blocks n < t // 4 with I[t, n] >= the index_topk-th best of them, and block t // 4
    softmax over the kept s <= t;  out = concat_h(sum_s p v_s) W_o

**FFN**: layers whose ``mlp_layer_types`` entry is ``dense`` a gated FFN
``intermediate_size`` wide; every other one routed experts behind a sigmoid
router over ALL ``published.n_routed_experts`` outputs (its bias moves the
choice only; ``n_group`` = ``topk_group`` = 1), the chosen scores
renormalised times ``routed_scaling_factor``, of which experts
``first_expert .. first_expert + n_routed_experts`` are held here (what the
others would add lives on other chips), plus the shared expert. Every gated
FFN is ``(silu(min(h G, lim)) * clip(h U, -lim, lim)) D``, ``lim`` =
``swiglu_limit``.

**Weights** (``make_weights``; the program's recipe leaf for leaf): ``keys =
split(PRNGKey(seed), 12)``; a matmul leaf is ``normal(key) / sqrt(fan_in)``
rounded to ``engine.dtype``, then int8 (int4 levels for the control) with one
float32 scale per output channel of each (layer, expert); the file's
``weights.init`` names every leaf's key and shape.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REQUIRED_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
                 "first_k_dense_replace", "layer_types", "mlp_layer_types", "linear_attn_config",
                 "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                 "v_head_dim", "mla_use_nope", "index_n_heads", "index_head_dim", "index_topk", "index_kpool",
                 "index_kpool_compress", "index_kpool_always_select_tail", "n_routed_experts", "n_shared_experts",
                 "num_experts_per_tok", "routed_scaling_factor", "scoring_func", "norm_topk_prob", "topk_method",
                 "n_group", "topk_group", "swiglu_limit", "hc_mult", "hc_sinkhorn_iters", "hc_eps", "rms_norm_eps",
                 "tie_word_embeddings", "vocab_size", "first_expert")

# the mixing logits' clamp of the residual maps: the config has no key for it (``assumed.mhc_clamp``)
HC_RES_CLAMP = 30.0
L2_EPS = 1e-6
# queries a block of DSA's reference scores: a block's ``[H, queries, T]`` float32 scores stay about a GB
# at a 9k context (one request's check far past the indexer's reach); the arithmetic is the same
QUERY_BLOCK = 512


def _sizes(c: Dict[str, Any]) -> Dict[str, Any]:
    la = c["linear_attn_config"]
    layers = int(c["num_hidden_layers"])
    types = list(c["layer_types"])[:layers]
    return {
        "d": int(c["hidden_size"]), "ff": int(c["intermediate_size"]), "fe": int(c["moe_intermediate_size"]),
        "layers": layers, "dense": int(c["first_k_dense_replace"]), "types": types,
        "kda": types.count("linear_attention"), "dsa": types.count("deepseek_sparse_attention"),
        "h": int(c["num_attention_heads"]), "rq": int(c["q_lora_rank"]), "rkv": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]), "v": int(c["v_head_dim"]),
        "hi": int(c["index_n_heads"]), "di": int(c["index_head_dim"]), "topk": int(c["index_topk"]),
        "kpool": int(c["index_kpool"]),
        "kh": int(la["num_heads"]), "kd": int(la["head_dim"]), "taps": int(la["short_conv_kernel_size"]),
        "lower": float(la["gate_lower_bound"]),
        "held": int(c["n_routed_experts"]), "first": int(c.get("first_expert", 0)),
        "routed": int(c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])),
        "shared": int(c["n_shared_experts"]), "top": int(c["num_experts_per_tok"]),
        "n": int(c["hc_mult"]), "vocab": int(c["vocab_size"]),
    }


def _kda_in_width(s) -> int:
    """``q | k | v``, ``beta`` a head, the two gates' first stages (rank ``K``)."""
    return 3 * s["kh"] * s["kd"] + s["kh"] + 2 * s["kd"]


def expert_layers(c: Dict[str, Any]) -> int:
    """Layers with an expert layer in them: all but the leading dense ones."""
    return int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])


def stand_in(c: Dict[str, Any]) -> Tuple[float, float]:
    """``(embedding's standard deviation, gain on the routed experts'
    down-projection)`` of the seeded weights: the file's ``stand_in`` group
    (absent: the recipe's, 0.02 and 1)."""
    group = c.get("stand_in", {})
    return float(group.get("embed_std", 0.02)), float(group.get("routed_down_gain", 1.0))


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ModelConfig``."""
    s = _sizes(c)
    la = c["linear_attn_config"]
    mlp = list(c["mlp_layer_types"])[: s["layers"]]
    if mlp != ["dense"] * s["dense"] + ["sparse"] * (s["layers"] - s["dense"]):
        raise ValueError("mlp_layer_types: first_k_dense_replace dense layers, then expert layers")
    kda_at = [i for i, t in enumerate(s["types"]) if t == "linear_attention"]
    if kda_at != [i for i in la["kda_layers"] if i < s["layers"]] or s["kda"] + s["dsa"] != s["layers"]:
        raise ValueError("layer_types names every layer linear_attention (linear_attn_config.kda_layers) "
                         "or deepseek_sparse_attention")
    if (c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" or int(c["n_group"]) != 1
            or int(c["topk_group"]) != 1 or not c["norm_topk_prob"]):
        raise ValueError("the glm53_flash family routes by sigmoid scores over one group, renormalised")
    if int(c["qk_rope_head_dim"]) or not c["mla_use_nope"] or c.get("attention_bias") or c["tie_word_embeddings"]:
        raise ValueError("the glm53_flash family's latent attention has no rotary part, no bias; an untied head")
    if not (c["index_kpool_compress"] and c["index_kpool_always_select_tail"]):
        raise ValueError("the indexer pools its keys a block at a time and always keeps the tail block")
    return dict(
        name=str(c["model"]), vocab_size=s["vocab"], d_model=s["d"], n_layers=s["layers"],
        n_heads=s["h"], n_kv_heads=1, d_head=s["nope"], d_ff=s["ff"], norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
        attention="latent", q_lora_rank=s["rq"], kv_lora_rank=s["rkv"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=0, v_head_dim=s["v"], position_embedding="none",
        index_n_heads=s["hi"], index_head_dim=s["di"], index_topk=s["topk"], index_kpool=s["kpool"],
        layer_types=tuple(s["types"]), kda_n_heads=s["kh"], kda_d_head=s["kd"], kda_d_conv=s["taps"],
        kda_lower_bound=s["lower"],
        n_dense_layers=s["dense"], d_ff_expert=s["fe"], n_experts=s["held"], first_expert=s["first"],
        router_width=s["routed"], top_k_experts=s["top"], n_shared_experts=s["shared"], router_scoring="sigmoid",
        router_bias=True, routed_scaling_factor=float(c["routed_scaling_factor"]), renormalize_topk=True,
        swiglu_limit=float(c["swiglu_limit"]),
        residual_streams=s["n"], hc_sinkhorn_iters=int(c["hc_sinkhorn_iters"]), hc_eps=float(c["hc_eps"]),
        hc_res_clamp=HC_RES_CLAMP,
        init_embed_std=stand_in(c)[0], init_routed_gain=stand_in(c)[1],
    )


def _quant(w, axis: int, levels: float):
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=axis, keepdims=True), 1e-8) / levels
    return {"q": jnp.clip(jnp.round(wf / scale), -levels, levels).astype(jnp.int8), "s": scale}


def make_weights(cfg: Dict[str, Any], seed: int, bits: int = 8):
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    s = _sizes(cfg)
    d, ff, fe, h, n = s["d"], s["ff"], s["fe"], s["h"], s["n"]
    rq, rkv, la, lk = s["rq"], s["rkv"], s["dsa"], s["kda"]
    lead, moe, outs, e = s["dense"], expert_layers(cfg), 2 * n + n * n, s["held"]
    kh, kd, di_k = s["kh"], s["kd"], s["kh"] * s["kd"]
    levels = 127.0 if bits == 8 else 7.0
    stored = jnp.dtype(cfg.get("engine", {}).get("dtype", "bfloat16"))
    plan = (  # leaf, key index, shape, fan-in
        ("w_qa", 1, (la, d, rq), d),
        ("w_qb", 10, (la, rq, h * s["nope"]), rq),
        ("w_kva", 2, (la, d, rkv), d),
        ("w_kvb", 3, (la, rkv, h * (s["nope"] + s["v"])), rkv),
        ("wo", 4, (la, h * s["v"], d), h * s["v"]),
        ("w_gate", 5, (lead, d, ff), d),
        ("w_up", 6, (lead, d, ff), d),
        ("w_down", 7, (lead, ff, d), ff),
    )
    index_plan = (("idx_wq", (la, rq, s["hi"] * s["di"]), rq), ("idx_wk", (la, d, s["di"]), d),
                  ("idx_ww", (la, d, s["hi"]), d))
    embed_std, routed_gain = stand_in(cfg)
    expert_plan = (("we_gate", (e, d, fe), d), ("we_up", (e, d, fe), d),
                   ("we_down", (e, fe, d), fe / routed_gain**2))  # the gain folded into the fan-in
    shared_plan = (("ws_gate", (moe, d, s["shared"] * fe), d), ("ws_up", (moe, d, s["shared"] * fe), d),
                   ("ws_down", (moe, s["shared"] * fe, d), s["shared"] * fe))
    kda_plan = (("kda_in", (d, _kda_in_width(s)), d), ("kda_out", (di_k, d), di_k))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(stored)

        def per_layer(k, count, shape, fan_in):  # a layer at a time: layer l from fold_in(k, l)
            return jax.lax.map(
                lambda li: _quant(mat(jax.random.fold_in(k, li), shape, fan_in), -2, levels), jnp.arange(count))

        embed = (jax.random.normal(keys[0], (s["vocab"], d), dtype=jnp.float32) * embed_std).astype(stored)
        out = {
            "embed": _quant(embed, -1, 127.0),
            "lm_head": _quant(mat(keys[8], (d, s["vocab"]), d), -2, 127.0),
            "router": mat(keys[9], (moe, d, s["routed"]), d).astype(jnp.float32),
            "router_bias": jax.random.normal(jax.random.fold_in(keys[9], 1), (moe, s["routed"]),
                                             dtype=jnp.float32) * 1e-2,
        }
        for name, ki, shape, fan_in in plan:
            out[name] = _quant(mat(keys[ki], shape, fan_in), -2, levels)
        for i, (name, shape, fan_in) in enumerate(index_plan):
            out[name] = _quant(mat(jax.random.fold_in(keys[1], 16 + i), shape, fan_in), -2, levels)
        for fold, (name, shape, fan_in) in enumerate(expert_plan):
            out[name] = per_layer(jax.random.fold_in(keys[11], fold), moe, shape, fan_in)
        for fold, (name, shape, fan_in) in enumerate(shared_plan):
            out[name] = _quant(mat(jax.random.fold_in(keys[11], 3 + fold), shape, fan_in), -2, levels)
        diagonal = jnp.concatenate([jnp.zeros((2 * n,)), 3.0 * jnp.eye(n).reshape(-1)])
        for which, sub in enumerate(("attn", "mlp")):
            hk = jax.random.fold_in(keys[11], 6 + which)
            out[f"hc_{sub}_phi"] = jax.random.normal(
                jax.random.fold_in(hk, 0), (s["layers"], n * d, outs), dtype=jnp.float32) / math.sqrt(n * d)
            out[f"hc_{sub}_alpha"] = jnp.ones((s["layers"], 3), dtype=jnp.float32)
            out[f"hc_{sub}_bias"] = jax.random.normal(
                jax.random.fold_in(hk, 1), (s["layers"], outs), dtype=jnp.float32) * 0.5 + diagonal
        kk = [jax.random.fold_in(keys[10], 16 + i) for i in range(7)]
        for i, (name, shape, fan_in) in enumerate(kda_plan):
            out[name] = per_layer(kk[i], lk, shape, fan_in)
        out["kda_fb"] = _quant(mat(kk[2], (lk, kd, di_k), kd), -2, levels)
        out["kda_gb"] = _quant(mat(kk[3], (lk, kd, di_k), kd), -2, levels)
        out["kda_conv_w"] = mat(kk[4], (lk, s["taps"], 3 * di_k), s["taps"]).astype(jnp.float32)
        out["kda_a_log"] = jnp.log(jax.random.uniform(kk[5], (lk, kh), jnp.float32, 1.0, 16.0))
        dt0 = jnp.exp(jax.random.uniform(kk[6], (lk, di_k), jnp.float32, math.log(1e-3), math.log(1e-1)))
        out["kda_dt_bias"] = dt0 + jnp.log(-jnp.expm1(-dt0))
        return out

    return build(jax.random.PRNGKey(seed))


def _deq(leaf):
    return leaf["q"].astype(jnp.float32) * leaf["s"]


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def sinkhorn(z, iters: int, eps: float):
    """``exp(z) [T, n, n]`` with its columns and then its rows divided by
    their sums (+ ``eps``), ``iters`` times."""
    m = jnp.exp(z)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def stream_map(cfg: Dict[str, Any], x, phi, alpha, bias):
    """``(Hp [T, n], Hq [T, n], Hr [T, n, n])`` of one sublayer for the streams ``x [T, n, d]``."""
    n, eps = int(cfg["hc_mult"]), float(cfg["hc_eps"])
    v = x.reshape(x.shape[0], -1)
    m = (v / jnp.sqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)) @ phi
    hp = jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n])
    hq = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n : 2 * n] + bias[n : 2 * n])
    z = jnp.clip(alpha[2] * m[:, 2 * n :].reshape(-1, n, n) + bias[2 * n :].reshape(n, n), -HC_RES_CLAMP,
                 HC_RES_CLAMP)
    return hp, hq, sinkhorn(z, int(cfg["hc_sinkhorn_iters"]), eps)


def index_keep(iq, iw, ik, topk: int, kpool: int, first: int = 0):
    """``[Q, T]`` bool: the positions each of the queries at ``first ..
    first + Q - 1`` attends under the indexer (``iq [Q, Hi, Di]``, ``iw [Q,
    Hi]``, the keys ``ik [T, Di]``, ``T`` a multiple of ``kpool``): the
    mean-pooled keys of whole blocks scored, the ``topk`` best kept (ties at
    the last kept: all), the query's own block always, causally."""
    length = ik.shape[0]
    blocks = length // kpool
    pooled = jnp.mean(ik.reshape(blocks, kpool, -1), axis=1)
    score = jnp.einsum("tj,tjn->tn", iw, jax.nn.relu(jnp.einsum("tjd,nd->tjn", iq, pooled)))
    t = first + jnp.arange(iq.shape[0])
    whole = jnp.arange(blocks)[None, :] < (t // kpool)[:, None]
    score = jnp.where(whole, score, -jnp.inf)
    kth = jax.lax.top_k(score, min(topk, blocks))[0][:, -1:]
    chosen = (whole & (score >= kth)) | (jnp.arange(blocks)[None, :] == (t // kpool)[:, None])
    return jnp.repeat(chosen, kpool, axis=1) & (jnp.arange(length)[None, :] <= t[:, None])


def _parts(cfg: Dict[str, Any], length: int):
    """The jitted pieces of one row of ``length`` tokens. The caller walks
    the layers in Python and hands each piece ONE sublayer's weights."""
    s = _sizes(cfg)
    h, nope, v = s["h"], s["nope"], s["v"]
    kh, kd = s["kh"], s["kd"]
    di_k = kh * kd
    eps = float(cfg["rms_norm_eps"])
    factor = float(cfg["routed_scaling_factor"])
    lim = float(cfg["swiglu_limit"])
    hi = jax.default_matmul_precision("highest")

    @jax.jit
    def embed(emb, tokens):
        x = emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens]
        return jnp.broadcast_to(x[:, None, :], (length, s["n"], s["d"]))  # the copy on every stream

    def glu(hn, gate, up, down):
        return (jax.nn.silu(jnp.minimum(hn @ gate, lim)) * jnp.clip(hn @ up, -lim, lim)) @ down

    def kda(hn, w):
        proj = hn @ _deq(w["kda_in"])
        qkv, b = proj[:, : 3 * di_k], proj[:, 3 * di_k : 3 * di_k + kh]
        fa, ga = proj[:, 3 * di_k + kh : 3 * di_k + kh + kd], proj[:, 3 * di_k + kh + kd :]
        f, gate = fa @ _deq(w["kda_fb"]), ga @ _deq(w["kda_gb"])
        taps = s["taps"]
        before = jnp.concatenate([jnp.zeros((taps - 1, 3 * di_k)), qkv], axis=0)  # zeros before the sequence
        c = jax.nn.silu(sum(w["kda_conv_w"][j] * before[j : j + length] for j in range(taps)))
        q = _l2(c[:, :di_k].reshape(length, kh, kd)) / math.sqrt(kd)
        k = _l2(c[:, di_k : 2 * di_k].reshape(length, kh, kd))
        vv = c[:, 2 * di_k :].reshape(length, kh, kd)
        beta = jax.nn.sigmoid(b)
        g = s["lower"] * jax.nn.sigmoid(
            jnp.exp(w["kda_a_log"])[:, None] * (f + w["kda_dt_bias"]).reshape(length, kh, kd))
        eye = jnp.eye(kd)

        def token(state, xs):  # the recurrence, one token at a time, every head: state [H, K, V]
            q_t, k_t, v_t, g_t, b_t = xs
            keep = eye[None] - b_t[:, None, None] * k_t[:, :, None] * k_t[:, None, :]  # I - beta k k^T
            state = (jnp.einsum("hij,hjv->hiv", keep, jnp.exp(g_t)[:, :, None] * state)
                     + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((kh, kd, kd), jnp.float32), (q, k, vv, g, beta))
        o = _norm(o, eps) * jax.nn.sigmoid(gate.reshape(length, kh, kd))
        return o.reshape(length, di_k) @ _deq(w["kda_out"])

    def dsa(hn, w):
        c_q = _norm(hn @ _deq(w["w_qa"]), eps)
        q = (c_q @ _deq(w["w_qb"])).reshape(length, h, nope)
        c_kv = _norm(hn @ _deq(w["w_kva"]), eps)
        expanded = (c_kv @ _deq(w["w_kvb"])).reshape(length, h, nope + v)
        iq = (c_q @ _deq(w["idx_wq"])).reshape(length, s["hi"], s["di"])
        iw, ik = hn @ _deq(w["idx_ww"]), hn @ _deq(w["idx_wk"])
        out = []
        for lo in range(0, length, QUERY_BLOCK):  # a block of queries at a time, every key
            at = slice(lo, min(length, lo + QUERY_BLOCK))
            scores = jnp.einsum("qhd,khd->hqk", q[at], expanded[..., :nope]) / math.sqrt(nope)
            keep = index_keep(iq[at], iw[at], ik, s["topk"], s["kpool"], lo)
            probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hqk,khd->qhd", probs, expanded[..., nope:]))
        return jnp.concatenate(out).reshape(length, h * v) @ _deq(w["wo"])

    def dense(hn, w):
        return glu(hn, _deq(w["w_gate"]), _deq(w["w_up"]), _deq(w["w_down"]))

    def experts(hn, w):
        scores = jax.nn.sigmoid(hn @ w["router"])  # all the published outputs
        _, top_i = jax.lax.top_k(scores + w["router_bias"], s["top"])
        chosen = jnp.sum(jax.nn.one_hot(top_i, s["routed"]), axis=1)  # [T, routed] 0 / 1
        picked = chosen * scores
        combine = factor * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        combine = combine[:, s["first"] : s["first"] + s["held"]]  # [T, held]: the others live elsewhere

        def one(acc, xs):  # one expert dequantized at a time
            gate, up, down, weight = xs
            return acc + weight[:, None] * glu(hn, _deq(gate), _deq(up), _deq(down)), None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(hn), (w["we_gate"], w["we_up"], w["we_down"], combine.T))
        return routed + glu(hn, _deq(w["ws_gate"]), _deq(w["ws_up"]), _deq(w["ws_down"]))

    def sublayer(f):
        @jax.jit
        def run(x, w):  # x [T, n, d]
            with hi:
                hp, hq, hr = stream_map(cfg, x, w["phi"], w["alpha"], w["bias"])
                y = f(_norm(jnp.einsum("ti,tid->td", hp, x), eps), w)
                return jnp.einsum("tij,tjd->tid", hr, x) + hq[:, :, None] * y[:, None, :]

        return run

    @jax.jit
    def head(x, lm_head):  # x [n_out, n, d]: the positions asked for
        with hi:
            return _norm(jnp.sum(x, axis=1), eps) @ _deq(lm_head)

    return embed, sublayer(kda), sublayer(dsa), sublayer(dense), sublayer(experts), head


KDA_LEAVES = ("kda_in", "kda_out", "kda_fb", "kda_gb", "kda_conv_w", "kda_a_log", "kda_dt_bias")
DSA_LEAVES = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo", "idx_wq", "idx_wk", "idx_ww")
FFN_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down", "router", "router_bias")


def _logits(cfg: Dict[str, Any], parts, weights, tokens, first: int, n: int):
    embed, kda, dsa, dense, experts, head = parts
    s = _sizes(cfg)

    def take(names, at, sub, layer):
        w = {name: jax.tree_util.tree_map(lambda a: a[at], weights[name]) for name in names}
        return {**w, **{k: weights[f"hc_{sub}_{k}"][layer] for k in ("phi", "alpha", "bias")}}

    x = embed(weights["embed"], tokens)
    seen = {"linear_attention": 0, "deepseek_sparse_attention": 0}
    for layer, kind in enumerate(s["types"]):
        if kind == "linear_attention":
            x = kda(x, take(KDA_LEAVES, seen[kind], "attn", layer))
        else:
            x = dsa(x, take(DSA_LEAVES, seen[kind], "attn", layer))
        seen[kind] += 1
        if layer < s["dense"]:
            x = dense(x, take(FFN_LEAVES, layer, "mlp", layer))
        else:
            x = experts(x, take(EXPERT_LEAVES, layer - s["dense"], "mlp", layer))
    return head(x[first : first + n], weights["lm_head"])


def served_logits(cfg: Dict[str, Any], weights, token_rows: Sequence[List[int]],
                  spans: Sequence[Tuple[int, int]]):
    """One request at a time, each padded to a multiple of 128 of its own
    (one set of compiled pieces for each padded length; the stack is causal,
    so what follows a position does not reach it); the head over the
    positions its span asks for only."""
    parts, out = {}, []
    for row, (first, n) in zip(token_rows, spans):
        toks = np.zeros(-(-len(row) // 128) * 128, dtype=np.int32)
        toks[: len(row)] = row
        if len(toks) not in parts:
            parts[len(toks)] = _parts(cfg, len(toks))
        out.append(_logits(cfg, parts[len(toks)], weights, jnp.asarray(toks), first, n))
    return out


# -- least bytes and FLOPs, from the configuration's shapes alone ---------------


def params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights: a KDA mixer's projections (``kda_proj``: the input
    projection, the gates' second stages, the output) and with its
    convolution (``kda``), a DSA mixer (its indexer among them, ``index``
    alone), a leading layer's dense FFN, the router and one expert (routed,
    or the shared one's unit), one residual map's ``phi``, the head."""
    s = _sizes(cfg)
    d, h, n, di_k = s["d"], s["h"], s["n"], s["kh"] * s["kd"]
    proj = d * _kda_in_width(s) + 2 * s["kd"] * di_k + di_k * d
    index = s["rq"] * s["hi"] * s["di"] + d * (s["di"] + s["hi"])
    return {
        "kda_proj": proj,
        "kda": proj + s["taps"] * 3 * di_k,
        "index": index,
        "dsa": (d * s["rq"] + s["rq"] * h * s["nope"] + d * s["rkv"] + s["rkv"] * h * (s["nope"] + s["v"])
                + h * s["v"] * d + index),
        "ffn": 3 * d * s["ff"],
        "router": d * s["routed"],
        "expert": 3 * d * s["fe"],
        "map": n * d * (2 * n + n * n),
        "head": d * s["vocab"],
    }


def map_bytes(cfg: Dict[str, Any]) -> int:
    """float32 bytes of the residual-stream maps: two a layer, ``phi``, three scalars, a bias an output."""
    n = int(cfg["hc_mult"])
    return 2 * int(cfg["num_hidden_layers"]) * 4 * (params(cfg)["map"] + 3 + 2 * n + n * n)


def outside_experts_params(cfg: Dict[str, Any]) -> int:
    """Everything a step reads whoever is routed where: the mixers, the
    leading dense FFNs, the shared experts and the routers."""
    s, p = _sizes(cfg), params(cfg)
    return (s["kda"] * p["kda"] + s["dsa"] * p["dsa"] + s["dense"] * p["ffn"]
            + expert_layers(cfg) * (s["shared"] * p["expert"] + p["router"]))


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """What is stored here: int8 codes of every matmul leaf (the held
    experts, both ends of this chip's vocabulary), the router and the
    convolution at one byte too (0.2% of the whole), the maps in float32;
    scales, norms and the KDA scalars are left out."""
    s, p = _sizes(cfg), params(cfg)
    return int(outside_experts_params(cfg) + expert_layers(cfg) * s["held"] * p["expert"] + 2 * p["head"]
               + map_bytes(cfg))


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """One cached row a token and DSA layer: the 512-wide compressed row and the index key after it."""
    s = _sizes(cfg)
    return s["dsa"] * (s["rkv"] + s["di"]) * itemsize


def latent_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Cached rows one decode step's attention reads, all DSA layers: the
    compressed rows alone (the indexer reads the index keys)."""
    s = _sizes(cfg)
    return context_tokens * s["dsa"] * s["rkv"] * 2


def state_bytes_per_row(cfg: Dict[str, Any], conv_itemsize: int = 2) -> int:
    """One row's recurrent state over the KDA layers, whatever its length:
    ``S [H, K, K]`` float32 and the convolution's last ``taps - 1`` inputs
    of ``q | k | v`` in the engine's dtype."""
    s = _sizes(cfg)
    di_k = s["kh"] * s["kd"]
    return s["kda"] * (s["kh"] * s["kd"] * s["kd"] * 4 + (s["taps"] - 1) * 3 * di_k * conv_itemsize)


def experts_touched(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts of ONE layer that ``rows`` tokens reach when each takes
    ``num_experts_per_tok`` of the published experts evenly and independently."""
    s = _sizes(cfg)
    return s["held"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** rows)


def expert_bytes(cfg: Dict[str, Any], touched: float) -> float:
    """int8 bytes of ``touched`` routed experts (summed over layers by the caller)."""
    return touched * params(cfg)["expert"]


def ssm_state_bytes(cfg: Dict[str, Any], rows: float) -> float:
    """The KDA state of ``rows`` rows read and written once, all KDA layers."""
    return 2.0 * rows * state_bytes_per_row(cfg)


def ssm_proj_bytes(cfg: Dict[str, Any]) -> int:
    """int8 bytes of the KDA mixers' projections (W_in, the gates' second stages, W_out), all layers."""
    return _sizes(cfg)["kda"] * params(cfg)["kda_proj"]


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float) -> float:
    """Least bytes one decode step moves: every weight outside the routed
    experts once (the head among them), of the held experts those that
    ``rows`` tokens touch under even, independent routing, the maps, the
    live rows' KDA state read and written, their cached rows (compressed row
    and index key), one row written a token, their embedding rows, the
    float32 logits."""
    s, p = _sizes(cfg), params(cfg)
    touched = expert_layers(cfg) * expert_bytes(cfg, experts_touched(cfg, rows))
    kv = kv_bytes_per_token(cfg)
    return (outside_experts_params(cfg) + p["head"] + map_bytes(cfg) + touched + ssm_state_bytes(cfg, rows)
            + context_tokens * kv + rows * kv + rows * s["d"] + rows * s["vocab"] * 4)


def _token_matmul_params(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token uses HERE: the mixers, the dense FFNs, the
    shared experts, the routers, its expected share of the held experts and
    the maps' ``phi``."""
    s, p = _sizes(cfg), params(cfg)
    return (outside_experts_params(cfg) + expert_layers(cfg) * s["top"] * s["held"] / s["routed"] * p["expert"]
            + 2 * s["layers"] * p["map"])


def _recurrence_flops(cfg: Dict[str, Any]) -> float:
    """A token through the KDA recurrences: every state value decayed, read
    against k, given k u and read against q (7 operations)."""
    s = _sizes(cfg)
    return 7.0 * s["kda"] * s["kh"] * s["kd"] * s["kd"]


def _attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """Absorbed form, per query token: scores over the compressed row and
    values over it, every head, over the positions the indexer keeps (all
    below ``index_topk`` blocks); the indexer scores every block."""
    s = _sizes(cfg)
    attended = min(context, s["topk"] * s["kpool"])
    return s["dsa"] * (2.0 * s["h"] * attended * 2 * s["rkv"] + 2.0 * s["hi"] * s["di"] * context / s["kpool"])


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    return (2.0 * (_token_matmul_params(cfg) + params(cfg)["head"]) + _recurrence_flops(cfg)
            + _attention_flops(cfg, context))


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """The layers for every token, causal attention (half the square), the head once."""
    return ((2.0 * _token_matmul_params(cfg) + _recurrence_flops(cfg)) * prompt_tokens
            + _attention_flops(cfg, (prompt_tokens + 1) / 2.0) * prompt_tokens + 2.0 * params(cfg)["head"])
