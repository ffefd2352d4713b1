"""The LongCat-Flash family, as one chip's share of an expert-parallel
deployment (``benchmark/configs/longcat-flash-ep32.json`` states which).

Plain reference of the served model in the EXPANDED form: every cached row
is expanded to its 64 key and value heads (the program serves the absorbed
form, so the two share no arithmetic), float32,
``default_matmul_precision("highest")``, no cache, no batching tricks. It
imports nothing of the program.

**One layer** (``num_layers`` counts these; all norms RMSNorm with a gain
of 1 by the recipe, so the gains are left out)::

    r = x;  x = r + MLA_0(norm(x))
    r = x;  h = norm(x);  s = MoE(h);  x = r + FFN_0(h)
    r = x;  x = r + MLA_1(norm(x))
    r = x;  x = r + FFN_1(norm(x))
    x = x + s

**MLA(h)** at position p::

    c_q = norm(h W_qa);  q = (c_q W_qb) as [H, nope + rope], x sqrt(d / q_lora_rank)
    [c_kv | k_rope] = h W_kva;  c_kv = norm(c_kv) x sqrt(d / kv_lora_rank)
    q_rope, k_rope = rope(., p)          # k_rope is ONE head shared by all H
    [k_nope | v] = (c_kv W_kvb) as [H, nope + v]
    score[t] = (q_nope . k_nope[t] + q_rope . k_rope[t]) / sqrt(nope + rope), causal softmax
    out = concat_h(sum_t P[t] v[t]) W_o

The cached row of the program is ``[c_kv | k_rope]`` rounded to the
engine's dtype; the reference rounds nothing.

**MoE(h)**, router ``published.n_routed_experts + zero_expert_num`` wide::

    p = softmax(float32(h) W_r);  I = top_k(p + b);  w_i = factor x p_i   (not renormalised)
    s = sum_{i in I, held here} w_i E_i(h)  +  sum_{i in I, identity} w_i h
    E_i(h) = (silu(h G_i) * (h U_i)) D_i

Held here are routed experts ``first_expert .. first_expert +
n_routed_experts``; a routed expert that is not held adds nothing, here as
in the program (the deployment's other chips add their own). No token is
dropped.

Weights by the program's ``init_params`` recipe, leaf for leaf: ``keys =
split(PRNGKey(seed), 12)``; a leaf of attention block or dense FFN ``j`` (0,
1) is named ``<leaf>_<j>``, shaped ``[num_layers, in, out]`` and drawn from
``fold_in(keys[i], j)`` (``w_qa`` i=1, ``w_kva`` 2, ``w_kvb`` 3, ``wo`` 4,
``w_gate`` 5, ``w_up`` 6, ``w_down`` 7, ``w_qb`` 10); the experts
``we_gate``, ``we_up``, ``we_down`` ``[num_layers, held, in, out]`` from
``fold_in(keys[11], 0 / 1 / 2)``; embedding ``keys[0]``, head ``keys[8]``,
router ``keys[9]``, router bias ``fold_in(keys[9], 1)``. Each leaf is
``normal(key) / sqrt(fan_in)`` in float32 (embedding ``* 0.02``, router bias
``* 1e-3`` and kept float32), rounded to ``engine.dtype``; matmul leaves then
int8 (``bits=4``: 4 bits, the control) with one float32 scale per output
channel of each (layer, expert); embedding per row, head per output channel;
router and bias not quantized.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REQUIRED_KEYS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
                 "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "zero_expert_num", "moe_topk",
                 "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta")
BLOCKS = 2  # attention blocks, and dense FFNs, of one layer


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    published = int(c.get("published", {}).get("n_routed_experts", c["n_routed_experts"]))
    return {
        "d": int(c["hidden_size"]), "ff": int(c["ffn_hidden_size"]), "fe": int(c["expert_ffn_hidden_size"]),
        "layers": int(c["num_layers"]), "h": int(c["num_attention_heads"]),
        "rq": int(c["q_lora_rank"]), "rkv": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]), "v": int(c["v_head_dim"]),
        "held": int(c["n_routed_experts"]), "first": int(c.get("first_expert", 0)),
        "routed": published, "zero": int(c["zero_expert_num"]), "top": int(c["moe_topk"]),
        "vocab": int(c["vocab_size"]),
    }


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ModelConfig``."""
    s = _sizes(c)
    return dict(
        name=str(c["model"]), vocab_size=s["vocab"], d_model=s["d"], n_layers=s["layers"],
        n_heads=s["h"], n_kv_heads=1, d_head=s["nope"] + s["rope"], d_ff=s["ff"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
        attention="latent", q_lora_rank=s["rq"], kv_lora_rank=s["rkv"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["v"],
        mla_scale_q_lora=bool(c.get("mla_scale_q_lora", False)),
        mla_scale_kv_lora=bool(c.get("mla_scale_kv_lora", False)),
        blocks_per_layer=BLOCKS, d_ff_expert=s["fe"],
        n_experts=s["held"], first_expert=s["first"], router_width=s["routed"] + s["zero"],
        n_zero_experts=s["zero"], top_k_experts=s["top"],
        routed_scaling_factor=float(c["routed_scaling_factor"]), renormalize_topk=False, router_bias=True,
    )


def _quant(w, axis: int, levels: float):
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=axis, keepdims=True), 1e-8) / levels
    return {"q": jnp.clip(jnp.round(wf / scale), -levels, levels).astype(jnp.int8), "s": scale}


def make_weights(cfg: Dict[str, Any], seed: int, bits: int = 8):
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    s = _sizes(cfg)
    d, ff, fe, layers, h, e = s["d"], s["ff"], s["fe"], s["layers"], s["h"], s["held"]
    rq, rkv, qk, up = s["rq"], s["rkv"], s["nope"] + s["rope"], s["nope"] + s["v"]
    width = s["routed"] + s["zero"]
    levels = 127.0 if bits == 8 else 7.0
    stored = jnp.dtype(cfg.get("engine", {}).get("dtype", "bfloat16"))
    block_plan = (  # leaf of a block, key index, shape, fan-in
        ("w_qa", 1, (layers, d, rq), d),
        ("w_qb", 10, (layers, rq, h * qk), rq),
        ("w_kva", 2, (layers, d, rkv + s["rope"]), d),
        ("w_kvb", 3, (layers, rkv, h * up), rkv),
        ("wo", 4, (layers, h * s["v"], d), h * s["v"]),
        ("w_gate", 5, (layers, d, ff), d),
        ("w_up", 6, (layers, d, ff), d),
        ("w_down", 7, (layers, ff, d), ff),
    )
    expert_plan = (  # leaf, fold of keys[11], shape, fan-in
        ("we_gate", 0, (layers, e, d, fe), d),
        ("we_up", 1, (layers, e, d, fe), d),
        ("we_down", 2, (layers, e, fe, d), fe),
    )

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(stored)

        embed = (jax.random.normal(keys[0], (s["vocab"], d), dtype=jnp.float32) * 0.02).astype(stored)
        out = {
            "embed": _quant(embed, -1, 127.0),
            "lm_head": _quant(mat(keys[8], (d, s["vocab"]), d), -2, 127.0),
            "router": mat(keys[9], (layers, d, width), d).astype(jnp.float32),
            "router_bias": jax.random.normal(jax.random.fold_in(keys[9], 1), (layers, width),
                                             dtype=jnp.float32) * 1e-3,
        }
        for j in range(BLOCKS):
            for name, ki, shape, fan_in in block_plan:
                out[f"{name}_{j}"] = _quant(mat(jax.random.fold_in(keys[ki], j), shape, fan_in), -2, levels)
        for name, fold, shape, fan_in in expert_plan:
            out[name] = _quant(mat(jax.random.fold_in(keys[11], fold), shape, fan_in), -2, levels)
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


def _parts(cfg: Dict[str, Any], length: int):
    """The jitted pieces of one row of ``length`` tokens. The caller walks
    the layers in Python and hands each piece ONE block's, FFN's or layer's
    weights, so that no more than one of them is dequantized at a time."""
    s = _sizes(cfg)
    d, h, rq, rkv, nope, rope, v = s["d"], s["h"], s["rq"], s["rkv"], s["nope"], s["rope"], s["v"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    scale_q = math.sqrt(d / rq) if cfg.get("mla_scale_q_lora") else 1.0
    scale_kv = math.sqrt(d / rkv) if cfg.get("mla_scale_kv_lora") else 1.0
    factor = float(cfg["routed_scaling_factor"])
    hi = jax.default_matmul_precision("highest")

    @jax.jit
    def embed(emb, tokens):
        return emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens]

    @jax.jit
    def attention(x, w):
        with hi:
            hn = _norm(x, eps)
            freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, rope // 2, dtype=jnp.float32) / (rope // 2))
            ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            q = (_norm(hn @ _deq(w["w_qa"]), eps) @ _deq(w["w_qb"])).reshape(length, h, nope + rope) * scale_q
            kv = hn @ _deq(w["w_kva"])
            c_kv = _norm(kv[:, :rkv], eps) * scale_kv
            k_rope = _rope(kv[:, None, rkv:], cos, sin)  # [T, 1, rope]
            q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cos, sin)
            expanded = (c_kv @ _deq(w["w_kvb"])).reshape(length, h, nope + v)
            k = jnp.concatenate([expanded[..., :nope], jnp.broadcast_to(k_rope, (length, h, rope))], axis=-1)
            scores = jnp.einsum("qhd,khd->hqk", jnp.concatenate([q_nope, q_rope], axis=-1), k)
            scores = scores / math.sqrt(nope + rope)
            causal = jnp.tril(jnp.ones((length, length), dtype=bool))
            probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("hqk,khd->qhd", probs, expanded[..., nope:]).reshape(length, h * v)
            return x + out @ _deq(w["wo"])

    @jax.jit
    def ffn(x, w):
        """``(x + FFN(norm(x)), norm(x))``: the normed input is the expert layer's too."""
        with hi:
            hn = _norm(x, eps)
            act = jax.nn.silu(hn @ _deq(w["w_gate"])) * (hn @ _deq(w["w_up"]))
            return x + act @ _deq(w["w_down"]), hn

    @jax.jit
    def experts(hn, w):
        with hi:
            probs = jax.nn.softmax(hn @ w["router"], axis=-1)
            _, top_i = jax.lax.top_k(probs + w["router_bias"], s["top"])
            # [T, width]: factor x p on the chosen outputs, zero elsewhere
            combine = jnp.sum(jax.nn.one_hot(top_i, probs.shape[-1]) * probs[:, None, :], axis=1) * factor
            identity = hn * jnp.sum(combine[:, s["routed"]:], axis=-1, keepdims=True)
            held = combine[:, s["first"] : s["first"] + s["held"]]  # the others are not here: nothing

            def one(acc, xs):  # one expert dequantized at a time
                gate, up, down, weight = xs
                y = (jax.nn.silu(hn @ _deq(gate)) * (hn @ _deq(up))) @ _deq(down)
                return acc + weight[:, None] * y, None

            routed, _ = jax.lax.scan(one, jnp.zeros_like(hn), (w["we_gate"], w["we_up"], w["we_down"], held.T))
            return routed + identity

    @jax.jit
    def head(x, lm_head):
        with hi:
            return _norm(x, eps) @ _deq(lm_head)

    return embed, attention, ffn, experts, head


ATTENTION_LEAVES = ("w_qa", "w_qb", "w_kva", "w_kvb", "wo")
FFN_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down", "router", "router_bias")


def _logits(cfg: Dict[str, Any], parts, weights, tokens):
    embed, attention, ffn, experts, head = parts

    def take(names, layer, block=None):
        sfx = "" if block is None else f"_{block}"
        return {n: jax.tree_util.tree_map(lambda a: a[layer], weights[n + sfx]) for n in names}

    x = embed(weights["embed"], tokens)
    for layer in range(_sizes(cfg)["layers"]):
        x = attention(x, take(ATTENTION_LEAVES, layer, 0))
        x, hn = ffn(x, take(FFN_LEAVES, layer, 0))
        shortcut = experts(hn, take(EXPERT_LEAVES, layer))
        x = attention(x, take(ATTENTION_LEAVES, layer, 1))
        x, _ = ffn(x, take(FFN_LEAVES, layer, 1))
        x = x + shortcut
    return head(x, weights["lm_head"])


def served_logits(cfg: Dict[str, Any], weights, token_rows: Sequence[List[int]],
                  spans: Sequence[Tuple[int, int]]):
    """One request at a time, each padded to a multiple of 128 of its own
    (one set of compiled pieces for each padded length)."""
    parts, out = {}, []
    for row, (first, n) in zip(token_rows, spans):
        toks = np.zeros(-(-len(row) // 128) * 128, dtype=np.int32)
        toks[: len(row)] = row
        if len(toks) not in parts:
            parts[len(toks)] = _parts(cfg, len(toks))
        out.append(_logits(cfg, parts[len(toks)], weights, jnp.asarray(toks))[first : first + n])
    return out


# -- least bytes and FLOPs, from the configuration's shapes alone ---------------


def params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Matmul weights: one latent block, one dense FFN, the router and one
    routed expert of a layer; the head (the embedding is as large)."""
    s = _sizes(cfg)
    d, h = s["d"], s["h"]
    return {
        "attention": (d * s["rq"] + s["rq"] * h * (s["nope"] + s["rope"]) + d * (s["rkv"] + s["rope"])
                      + s["rkv"] * h * (s["nope"] + s["v"]) + h * s["v"] * d),
        "ffn": 3 * d * s["ff"],
        "router": d * (s["routed"] + s["zero"]),
        "expert": 3 * d * s["fe"],
        "head": d * s["vocab"],
    }


def layer_params_outside_experts(cfg: Dict[str, Any]) -> int:
    p = params(cfg)
    return BLOCKS * (p["attention"] + p["ffn"]) + p["router"]


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """int8 codes of what is stored here (held experts, the vocabulary's
    slice: embedding and head), the router in the engine's 2 bytes; the
    float32 scales and the norms are left out."""
    s, p = _sizes(cfg), params(cfg)
    per_layer = layer_params_outside_experts(cfg) + p["router"] + s["held"] * p["expert"]
    return s["layers"] * per_layer + 2 * p["head"]


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """One latent row a token and block: ``kv_lora_rank + qk_rope_head_dim`` values."""
    s = _sizes(cfg)
    return s["layers"] * BLOCKS * (s["rkv"] + s["rope"]) * itemsize


def experts_touched(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts of a layer that ``rows`` tokens reach when each takes
    ``moe_topk`` of the router's outputs evenly: the least that have to be read."""
    s = _sizes(cfg)
    return s["held"] * (1.0 - (1.0 - s["top"] / (s["routed"] + s["zero"])) ** rows)


def expert_bytes(cfg: Dict[str, Any], touched: float) -> float:
    """int8 bytes of ``touched`` routed experts (summed over layers by the caller)."""
    return touched * params(cfg)["expert"]


def latent_bytes(cfg: Dict[str, Any], context_tokens: float) -> float:
    """Cached rows one decode step's attention reads, all blocks of all layers."""
    return context_tokens * kv_bytes_per_token(cfg)


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float) -> float:
    """Least bytes one decode step moves: every weight outside the experts
    once, of the held experts the share ``rows`` tokens touch under even
    routing, the head over the vocabulary's slice, each live row's embedding
    row and cached rows, one row written a token, the float32 logits."""
    s, p = _sizes(cfg), params(cfg)
    layers = s["layers"] * (layer_params_outside_experts(cfg) + p["router"]
                            + expert_bytes(cfg, experts_touched(cfg, rows)))
    kv = kv_bytes_per_token(cfg)
    return (layers + p["head"] + rows * s["d"] + latent_bytes(cfg, context_tokens) + rows * kv
            + rows * s["vocab"] * 4)


def _token_matmul_params(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token uses: the blocks, the router, and of its
    ``moe_topk`` choices the expected share that lands on held experts."""
    s, p = _sizes(cfg), params(cfg)
    held_choices = s["top"] * s["held"] / (s["routed"] + s["zero"])
    return s["layers"] * (layer_params_outside_experts(cfg) + held_choices * p["expert"])


def _attention_flops(cfg: Dict[str, Any], context: float) -> float:
    """Absorbed form, per query token: scores over the row's whole width,
    values over its first ``kv_lora_rank`` columns, every head."""
    s = _sizes(cfg)
    return 2.0 * s["layers"] * BLOCKS * s["h"] * context * (2 * s["rkv"] + s["rope"])


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    return 2.0 * (_token_matmul_params(cfg) + params(cfg)["head"]) + _attention_flops(cfg, context)


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """The blocks for every token, causal attention (half the square), the head once."""
    return (2.0 * _token_matmul_params(cfg) * prompt_tokens
            + _attention_flops(cfg, (prompt_tokens + 1) / 2.0) * prompt_tokens + 2.0 * params(cfg)["head"])
