"""The Xing4.0 family (``benchmark/configs/xing4-29b-a4b-pp4.json`` states the
deployment and what of the model this chip holds).

Plain reference of the served model, written layer by layer in a Python
loop: the residual streams as an explicit ``[T, n, d]`` array, the Sinkhorn
projection as ``hc_sinkhorn_iters`` written-out steps on ``[T, n, n]``,
attention in the EXPANDED form (every cached row expanded to its key and
value heads; the program serves the absorbed form, so the two share no
arithmetic), float32, ``default_matmul_precision("highest")``, no cache, one
expert dequantized at a time. It imports nothing of the program.

**The residual path** (manifold-constrained hyper-connections,
arXiv:2512.24880 section 4.2; ``hc_mult`` is its expansion rate ``n``,
``hc_sinkhorn_iters`` its ``t_max``). A token's state is ``X [n, d]``;
``X_0[i] = embed(token)`` for every ``i`` and the stack's output is ``sum_i
X[i]`` (Hyper-Connections, arXiv:2409.19606). Every sublayer ``F``
(attention, then the FFN, of every layer) has its own ``phi [n d, 2 n + n
n]``, scalars ``a_pre, a_post, a_res`` and biases ``b_pre, b_post [n]``,
``b_res [n, n]``, all float32::

    v  = vec(X) (stream-major);  v' = v / sqrt(mean(v^2) + hc_eps)     # no gain: one would fold into phi
    m  = v' phi
    Hp = sigmoid(a_pre m[0:n] + b_pre);   Hq = 2 sigmoid(a_post m[n:2n] + b_post)
    Z  = clip(a_res mat(m[2n:]) + b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)   # row-major; on the logits
    M  = exp(Z);  hc_sinkhorn_iters times:  M = M / (colsum(M) + hc_eps);  M = M / (rowsum(M) + hc_eps)
    u  = sum_i Hp[i] X[i];   y = F(norm(u));   X'[i] = sum_j M[i, j] X[j] + Hq[i] y

**A leading layer** (``first_k_dense_replace`` of them): MLA, then the dense
gated-silu FFN ``intermediate_size`` wide. **An expert layer**: MLA, then MoE.
All norms RMSNorm with a gain of 1 by the recipe, so the gains are left out.

**MLA(h)** at position p::

    c_q = norm(h W_qa);  q = (c_q W_qb) as [H, nope + rope]
    [c_kv | k_rope] = h W_kva;  c_kv = norm(c_kv)
    q_rope, k_rope = rope(., p)          # k_rope is ONE head shared by all H; YaRN frequencies
    [k_nope | v] = (c_kv W_kvb) as [H, nope + v]
    score[t] = (q_nope . k_nope[t] + q_rope . k_rope[t]) x m(s, mscale_all_dim)^2 / sqrt(nope + rope), causal softmax
    out = concat_h(sum_t P[t] v[t]) W_o

YaRN (``rope_scaling``, DeepSeek-V3's form over the ``rope`` dimensions, ``i``
in ``0 .. rope / 2``, at every position)::

    f_i = theta^(-2i / rope);  corr(r) = rope ln(original / (2 pi r)) / (2 ln theta)
    low = floor(corr(beta_fast));  high = ceil(corr(beta_slow));  ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = f_i (1 - ramp_i) + (f_i / s) ramp_i;   m(s, a) = 0.1 a ln(s) + 1
    cos, sin x m(s, mscale) / m(s, mscale_all_dim)

**MoE(h)**::

    s = sigmoid(float32(h) W_r);  I = top_k(s + b)        # b moves the choice only; n_group = topk_group = 1
    w_i = routed_scaling_factor x s_i / (sum_{j in I} s_j + 1e-20)        # norm_topk_prob
    out = sum_{i in I} w_i E_i(h) + E_shared(h);   E(h) = (silu(h G) * (h U)) D

No token is dropped; every routed expert is held here.

Weights by the program's ``init_params`` recipe, leaf for leaf: ``keys =
split(PRNGKey(seed), 12)``; every matmul leaf ``normal(key) / sqrt(fan_in)``
in float32, rounded to ``engine.dtype``, then int8 (``bits=4``: 4 bits, the
control) with one float32 scale per output channel of each (layer, expert).
Attention leaves ``[layers, in, out]``: ``w_qa`` keys[1], ``w_qb`` keys[10],
``w_kva`` keys[2], ``w_kvb`` keys[3], ``wo`` keys[4]; the leading layers' FFN
``[first_k_dense_replace, in, out]``: ``w_gate`` keys[5], ``w_up`` keys[6],
``w_down`` keys[7]; the experts ``[expert layers, experts, in, out]``, layer
``l`` of leaf ``k`` (``we_gate`` 0, ``we_up`` 1, ``we_down`` 2) from
``fold_in(fold_in(keys[11], k), l)``, ``we_down`` times the file's
``stand_in.routed_down_gain``; the shared expert ``[expert layers, in,
out]`` ``ws_gate``, ``ws_up``, ``ws_down`` from ``fold_in(keys[11], 3 / 4 /
5)``; the router keys[9] ``[expert layers, d, experts]`` (kept in
``engine.dtype``, scored in float32), its bias ``fold_in(keys[9], 1)`` normal
``* 1e-2`` float32; embedding keys[0] ``* stand_in.embed_std`` (int8 per row), head keys[8]
(int8 per output channel). The maps, float32 and never quantized, sublayer
``attn`` 0 / ``mlp`` 1 from ``hk = fold_in(keys[11], 6 + sublayer)``:
``hc_<sub>_phi [layers, n d, 2 n + n n]`` ``normal(fold_in(hk, 0)) / sqrt(n
d)``, ``hc_<sub>_alpha [layers, 3]`` (``a_pre, a_post, a_res``) ones,
``hc_<sub>_bias [layers, 2 n + n n]`` (``b_pre | b_post | b_res`` row-major)
``normal(fold_in(hk, 1)) * 0.5``, ``+ 3`` on ``b_res``'s diagonal.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REQUIRED_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
                 "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                 "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "n_shared_experts",
                 "num_experts_per_tok", "routed_scaling_factor", "scoring_func", "norm_topk_prob", "hc_mult",
                 "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max", "rope_scaling",
                 "rope_theta", "rms_norm_eps", "vocab_size")


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    return {
        "d": int(c["hidden_size"]), "ff": int(c["intermediate_size"]), "fe": int(c["moe_intermediate_size"]),
        "layers": int(c["num_hidden_layers"]), "dense": int(c["first_k_dense_replace"]),
        "h": int(c["num_attention_heads"]), "rq": int(c["q_lora_rank"]), "rkv": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]), "v": int(c["v_head_dim"]),
        "experts": int(c["n_routed_experts"]), "shared": int(c["n_shared_experts"]),
        "top": int(c["num_experts_per_tok"]), "n": int(c["hc_mult"]), "vocab": int(c["vocab_size"]),
    }


def expert_layers(c: Dict[str, Any]) -> int:
    """Layers with an expert layer in them: all but the leading dense ones."""
    return int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"])


def stand_in(c: Dict[str, Any]) -> Tuple[float, float]:
    """``(embedding's standard deviation, gain on the routed experts'
    down-projection)`` of the seeded weights: the file's ``stand_in`` group
    (absent: the recipe every other configuration uses, 0.02 and 1)."""
    group = c.get("stand_in", {})
    return float(group.get("embed_std", 0.02)), float(group.get("routed_down_gain", 1.0))


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ModelConfig``."""
    s, rs = _sizes(c), c["rope_scaling"]
    if c["scoring_func"] != "sigmoid" or rs.get("type") != "yarn" or int(c.get("n_group", 1)) != 1:
        raise ValueError("the xing4 family is a sigmoid router over one group and YaRN rope scaling")
    if float(c["mhc_h_res_clamp_min"]) != -float(c["mhc_h_res_clamp_max"]):
        raise ValueError("the mixing logits' clamp is symmetric")
    return dict(
        name=str(c["model"]), vocab_size=s["vocab"], d_model=s["d"], n_layers=s["layers"],
        n_heads=s["h"], n_kv_heads=1, d_head=s["nope"] + s["rope"], d_ff=s["ff"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
        attention="latent", q_lora_rank=s["rq"], kv_lora_rank=s["rkv"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v"],
        n_dense_layers=s["dense"], d_ff_expert=s["fe"], n_experts=s["experts"], top_k_experts=s["top"],
        n_shared_experts=s["shared"], router_scoring="sigmoid", router_bias=True,
        routed_scaling_factor=float(c["routed_scaling_factor"]), renormalize_topk=bool(c["norm_topk_prob"]),
        residual_streams=s["n"], hc_sinkhorn_iters=int(c["hc_sinkhorn_iters"]), hc_eps=float(c["hc_eps"]),
        hc_res_clamp=float(c["mhc_h_res_clamp_max"]),
        rope_scaling=dict(
            factor=float(rs["factor"]), original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
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
    d, ff, fe, layers, h, e, n = s["d"], s["ff"], s["fe"], s["layers"], s["h"], s["experts"], s["n"]
    rq, rkv, qk, up = s["rq"], s["rkv"], s["nope"] + s["rope"], s["nope"] + s["v"]
    lead, moe, outs = s["dense"], expert_layers(cfg), 2 * n + n * n
    levels = 127.0 if bits == 8 else 7.0
    stored = jnp.dtype(cfg.get("engine", {}).get("dtype", "bfloat16"))
    plan = (  # leaf, key index, shape, fan-in
        ("w_qa", 1, (layers, d, rq), d),
        ("w_qb", 10, (layers, rq, h * qk), rq),
        ("w_kva", 2, (layers, d, rkv + s["rope"]), d),
        ("w_kvb", 3, (layers, rkv, h * up), rkv),
        ("wo", 4, (layers, h * s["v"], d), h * s["v"]),
        ("w_gate", 5, (lead, d, ff), d),
        ("w_up", 6, (lead, d, ff), d),
        ("w_down", 7, (lead, ff, d), ff),
    )
    embed_std, routed_gain = stand_in(cfg)
    expert_plan = (("we_gate", (e, d, fe), d), ("we_up", (e, d, fe), d),
                   ("we_down", (e, fe, d), fe / routed_gain**2))  # the gain folded into the fan-in, as the program does
    shared_plan = (("ws_gate", (moe, d, s["shared"] * fe), d), ("ws_up", (moe, d, s["shared"] * fe), d),
                   ("ws_down", (moe, s["shared"] * fe, d), s["shared"] * fe))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(stored)

        embed = (jax.random.normal(keys[0], (s["vocab"], d), dtype=jnp.float32) * embed_std).astype(stored)
        out = {
            "embed": _quant(embed, -1, 127.0),
            "lm_head": _quant(mat(keys[8], (d, s["vocab"]), d), -2, 127.0),
            "router": mat(keys[9], (moe, d, e), d).astype(jnp.float32),
            "router_bias": jax.random.normal(jax.random.fold_in(keys[9], 1), (moe, e), dtype=jnp.float32) * 1e-2,
        }
        for name, ki, shape, fan_in in plan:
            out[name] = _quant(mat(keys[ki], shape, fan_in), -2, levels)
        for fold, (name, shape, fan_in) in enumerate(expert_plan):
            ek = jax.random.fold_in(keys[11], fold)
            # a layer at a time: all layers of one leaf at once are gigabytes in float32
            out[name] = jax.lax.map(
                lambda li, ek=ek, shape=shape, fan_in=fan_in: _quant(
                    mat(jax.random.fold_in(ek, li), shape, fan_in), -2, levels),
                jnp.arange(moe))
        for fold, (name, shape, fan_in) in enumerate(shared_plan):
            out[name] = _quant(mat(jax.random.fold_in(keys[11], 3 + fold), shape, fan_in), -2, levels)
        diagonal = jnp.concatenate([jnp.zeros((2 * n,)), 3.0 * jnp.eye(n).reshape(-1)])
        for which, sub in enumerate(("attn", "mlp")):
            hk = jax.random.fold_in(keys[11], 6 + which)
            out[f"hc_{sub}_phi"] = jax.random.normal(
                jax.random.fold_in(hk, 0), (layers, n * d, outs), dtype=jnp.float32) / math.sqrt(n * d)
            out[f"hc_{sub}_alpha"] = jnp.ones((layers, 3), dtype=jnp.float32)
            out[f"hc_{sub}_bias"] = jax.random.normal(
                jax.random.fold_in(hk, 1), (layers, outs), dtype=jnp.float32) * 0.5 + diagonal
        return out

    return build(jax.random.PRNGKey(seed))


def _deq(leaf):
    return leaf["q"].astype(jnp.float32) * leaf["s"]


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    """The program's ``ops/rope.py`` pairing: the halves rotate against each other."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def yarn_mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(cfg: Dict[str, Any]) -> Tuple[np.ndarray, int, int, float, float]:
    """``(inv_freq [rope / 2], low, high, magnitude of cos and sin, score
    factor)`` of the configuration's YaRN scaling, in float64."""
    rs, rope, theta = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    factor, original = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def corr(turns: float) -> float:
        return rope * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), rope - 1)
    i = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rope)
    ramp = np.clip((i - low) / (high - low if high != low else 0.001), 0.0, 1.0)
    m_all = yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return (f * (1.0 - ramp) + (f / factor) * ramp, low, high,
            yarn_mscale(factor, float(rs["mscale"])) / m_all, m_all * m_all)


def score_scale(cfg: Dict[str, Any]) -> float:
    """What a score is multiplied by: ``m(s, mscale_all_dim)^2 / sqrt(nope + rope)``."""
    return yarn(cfg)[4] / math.sqrt(int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]))


def sinkhorn(z, iters: int, eps: float):
    """``exp(z) [T, n, n]`` with its columns and then its rows divided by
    their sums (+ ``eps``), ``iters`` times: doubly stochastic to the
    iteration's accuracy, its rows summing to 1."""
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
    z = alpha[2] * m[:, 2 * n :].reshape(-1, n, n) + bias[2 * n :].reshape(n, n)
    z = jnp.clip(z, float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"]))
    return hp, hq, sinkhorn(z, int(cfg["hc_sinkhorn_iters"]), eps)


def _parts(cfg: Dict[str, Any], length: int):
    """The jitted pieces of one row of ``length`` tokens. The caller walks
    the layers in Python and hands each piece ONE sublayer's weights, so
    that no more than one of them is dequantized at a time."""
    s = _sizes(cfg)
    h, rkv, nope, rope, v = s["h"], s["rkv"], s["nope"], s["rope"], s["v"]
    eps = float(cfg["rms_norm_eps"])
    factor = float(cfg["routed_scaling_factor"])
    inv_freq, _, _, magnitude, _ = yarn(cfg)
    scale = score_scale(cfg)
    hi = jax.default_matmul_precision("highest")

    @jax.jit
    def embed(emb, tokens):
        x = emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens]
        return jnp.broadcast_to(x[:, None, :], (length, s["n"], s["d"]))  # the copy on every stream

    def attention(hn, w):
        ang = jnp.arange(length, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
        cos, sin = (jnp.cos(ang) * magnitude)[:, None, :], (jnp.sin(ang) * magnitude)[:, None, :]
        q = (_norm(hn @ _deq(w["w_qa"]), eps) @ _deq(w["w_qb"])).reshape(length, h, nope + rope)
        kv = hn @ _deq(w["w_kva"])
        c_kv = _norm(kv[:, :rkv], eps)
        k_rope = _rope(kv[:, None, rkv:], cos, sin)  # [T, 1, rope]
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cos, sin)
        expanded = (c_kv @ _deq(w["w_kvb"])).reshape(length, h, nope + v)
        k = jnp.concatenate([expanded[..., :nope], jnp.broadcast_to(k_rope, (length, h, rope))], axis=-1)
        scores = jnp.einsum("qhd,khd->hqk", jnp.concatenate([q_nope, q_rope], axis=-1), k) * scale
        causal = jnp.tril(jnp.ones((length, length), dtype=bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, expanded[..., nope:]).reshape(length, h * v) @ _deq(w["wo"])

    def dense(hn, w):
        return (jax.nn.silu(hn @ _deq(w["w_gate"])) * (hn @ _deq(w["w_up"]))) @ _deq(w["w_down"])

    def experts(hn, w):
        scores = jax.nn.sigmoid(hn @ w["router"])
        _, top_i = jax.lax.top_k(scores + w["router_bias"], s["top"])
        chosen = jnp.sum(jax.nn.one_hot(top_i, s["experts"]), axis=1)  # [T, experts] 0 / 1
        picked = chosen * scores
        combine = factor * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

        def one(acc, xs):  # one expert dequantized at a time
            gate, up, down, weight = xs
            y = (jax.nn.silu(hn @ _deq(gate)) * (hn @ _deq(up))) @ _deq(down)
            return acc + weight[:, None] * y, None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(hn), (w["we_gate"], w["we_up"], w["we_down"], combine.T))
        shared = (jax.nn.silu(hn @ _deq(w["ws_gate"])) * (hn @ _deq(w["ws_up"]))) @ _deq(w["ws_down"])
        return routed + shared

    def sublayer(f):
        @jax.jit
        def run(x, w):  # x [T, n, d]
            with hi:
                hp, hq, hr = stream_map(cfg, x, w["phi"], w["alpha"], w["bias"])
                u = jnp.einsum("ti,tid->td", hp, x)
                y = f(_norm(u, eps), w)
                return jnp.einsum("tij,tjd->tid", hr, x) + hq[:, :, None] * y[:, None, :]

        return run

    @jax.jit
    def head(x, lm_head):  # x [n_out, n, d]: the positions asked for
        with hi:
            return _norm(jnp.sum(x, axis=1), eps) @ _deq(lm_head)

    return embed, sublayer(attention), sublayer(dense), sublayer(experts), head


ATTENTION_LEAVES = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")
FFN_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down", "router", "router_bias")


def _logits(cfg: Dict[str, Any], parts, weights, tokens, first: int, n: int):
    embed, attention, dense, experts, head = parts
    lead = int(cfg["first_k_dense_replace"])

    def take(names, at, sub, layer):
        w = {name: jax.tree_util.tree_map(lambda a: a[at], weights[name]) for name in names}
        return {**w, **{k: weights[f"hc_{sub}_{k}"][layer] for k in ("phi", "alpha", "bias")}}

    x = embed(weights["embed"], tokens)
    for layer in range(int(cfg["num_hidden_layers"])):
        x = attention(x, take(ATTENTION_LEAVES, layer, "attn", layer))
        if layer < lead:
            x = dense(x, take(FFN_LEAVES, layer, "mlp", layer))
        else:
            x = experts(x, take(EXPERT_LEAVES, layer - lead, "mlp", layer))
    return head(x[first : first + n], weights["lm_head"])


def served_logits(cfg: Dict[str, Any], weights, token_rows: Sequence[List[int]],
                  spans: Sequence[Tuple[int, int]]):
    """One request at a time, each padded to a multiple of 128 of its own
    (one set of compiled pieces for each padded length); the head over the
    positions its span asks for only (131,072 float32 logits a position)."""
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
    """Matmul weights: one latent block, one leading layer's dense FFN, the
    router and one expert (routed, or the shared one's unit) of an expert
    layer, one residual-stream map's ``phi``; the head (the embedding is as
    large)."""
    s = _sizes(cfg)
    d, h, n = s["d"], s["h"], s["n"]
    return {
        "attention": (d * s["rq"] + s["rq"] * h * (s["nope"] + s["rope"]) + d * (s["rkv"] + s["rope"])
                      + s["rkv"] * h * (s["nope"] + s["v"]) + h * s["v"] * d),
        "ffn": 3 * d * s["ff"],
        "router": d * s["experts"],
        "expert": 3 * d * s["fe"],
        "map": n * d * (2 * n + n * n),
        "head": d * s["vocab"],
    }


def map_bytes(cfg: Dict[str, Any]) -> int:
    """float32 bytes of the residual-stream maps: two a layer, ``phi``, three scalars, a bias an output."""
    n = int(cfg["hc_mult"])
    return 2 * int(cfg["num_hidden_layers"]) * 4 * (params(cfg)["map"] + 3 + 2 * n + n * n)


def dense_layer_params(cfg: Dict[str, Any]) -> int:
    """A leading layer: its latent block and its dense FFN."""
    p = params(cfg)
    return p["attention"] + p["ffn"]


def expert_layer_params(cfg: Dict[str, Any], experts: float) -> float:
    """An expert layer with ``experts`` routed experts counted: its latent
    block, the router, those experts and the shared ones."""
    s, p = _sizes(cfg), params(cfg)
    return p["attention"] + p["router"] + (experts + s["shared"]) * p["expert"]


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """What is stored here: int8 codes of every matmul leaf (all the routed
    experts, the shared one, both ends of the vocabulary), the router in the
    engine's 2 bytes, the maps in float32; the scales and the norms are left
    out."""
    s, p = _sizes(cfg), params(cfg)
    moe = expert_layers(cfg)
    return int(s["dense"] * dense_layer_params(cfg) + moe * (expert_layer_params(cfg, s["experts"]) + p["router"])
               + 2 * p["head"] + map_bytes(cfg))


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """One latent row a token and layer: ``kv_lora_rank + qk_rope_head_dim`` values."""
    s = _sizes(cfg)
    return s["layers"] * (s["rkv"] + s["rope"]) * itemsize


def experts_touched(cfg: Dict[str, Any], rows: float) -> float:
    """Routed experts of ONE layer that ``rows`` tokens reach when each takes
    ``num_experts_per_tok`` of them evenly and independently. Rows that
    route alike reach fewer, so for a step this is an estimate from above
    of what has to be read (PERF.md section 3 gives the cell's measured
    count beside it)."""
    s = _sizes(cfg)
    return s["experts"] * (1.0 - (1.0 - s["top"] / s["experts"]) ** rows)


def expert_bytes(cfg: Dict[str, Any], touched: float) -> float:
    """int8 bytes of ``touched`` routed experts (summed over layers by the caller)."""
    return touched * params(cfg)["expert"]


def latent_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Cached rows one decode step's attention reads, all layers."""
    return context_tokens * kv_bytes_per_token(cfg)


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float) -> float:
    """Least bytes one decode step moves: every weight outside the routed
    experts once (the head over the whole vocabulary among them), of the
    routed experts those that ``rows`` tokens touch under even, independent
    routing (``experts_touched``: the signature carries no measured count, so
    ``step.hbm_roofline`` is high by the share that the measured count lies
    under it), each live row's embedding row and cached rows, one row
    written a token, the float32 logits."""
    s, p = _sizes(cfg), params(cfg)
    moe = expert_layers(cfg)
    outside = s["dense"] * dense_layer_params(cfg) + moe * (expert_layer_params(cfg, 0) + p["router"])
    touched = moe * expert_bytes(cfg, experts_touched(cfg, rows))
    kv = kv_bytes_per_token(cfg)
    return (outside + map_bytes(cfg) + touched + p["head"] + rows * s["d"] + latent_bytes(cfg, context_tokens)
            + rows * kv + rows * s["vocab"] * 4)


def _token_matmul_params(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token uses: the layers with its ``num_experts_per_tok``
    routed experts and the shared ones, and the maps' ``phi``."""
    s, p = _sizes(cfg), params(cfg)
    return (s["dense"] * dense_layer_params(cfg) + expert_layers(cfg) * expert_layer_params(cfg, s["top"])
            + 2 * s["layers"] * p["map"])


def _attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """Absorbed form, per query token: scores over the row's whole width,
    values over its first ``kv_lora_rank`` columns, every head."""
    s = _sizes(cfg)
    return 2.0 * s["layers"] * s["h"] * context * (2 * s["rkv"] + s["rope"])


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    return 2.0 * (_token_matmul_params(cfg) + params(cfg)["head"]) + _attention_flops(cfg, context)


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """The layers for every token, causal attention (half the square), the head once."""
    return (2.0 * _token_matmul_params(cfg) * prompt_tokens
            + _attention_flops(cfg, (prompt_tokens + 1) / 2.0) * prompt_tokens + 2.0 * params(cfg)["head"])
