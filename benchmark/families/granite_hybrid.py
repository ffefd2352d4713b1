"""The Granite 4.0-H family (``benchmark/configs/granite-4h-small-ep8.json``
states the deployment and what of the model this chip holds): a stack of
Mamba-2 state-space layers with a few attention layers among them, every
layer's FFN routed experts beside a shared MLP.

Plain reference of the served model, written layer by layer in a Python
loop, float32, ``default_matmul_precision("highest")``, one layer
dequantized at a time. The state-space recurrence is a plain scan over
TOKENS: no chunked form, no cache, no state handed in; every request is
computed from position 0. It imports nothing of the program.

``d`` = ``hidden_size``; RMSNorm ``eps`` = ``rms_norm_eps``, gains 1 by the
recipe and left out (but the final norm's, ``stand_in.final_norm_gain``, which
multiplies the logits); ``layer_types[l]`` says what layer ``l``'s mixer is::

    x_0 = embedding_multiplier * embed(token)
    layer l:  x = x + residual_multiplier * Mixer_l(norm(x))
              h = norm(x);  x = x + residual_multiplier * (MoE(h) + Shared(h))
    logits = (norm(x) embed^T) / logits_scaling                  # tied head

**Mamba-2 mixer** (arXiv:2405.21060; ``H`` = ``mamba_n_heads``, ``P`` =
``mamba_d_head``, ``N`` = ``mamba_d_state``, ``G`` = ``mamba_n_groups``, ``K`` =
``mamba_d_conv``; ``d_in`` = ``H P``, convolution width ``C`` = ``d_in + 2 G N``)::

    [z | xBC | dt] = u W_in                                      # d_in | C | H, no bias
    c_t  = silu(b_conv + sum_{k<K} w_conv[k] * xBC_{t-(K-1)+k})  # depthwise, causal, zeros before the sequence
    [x | B | C] = c_t                                            # [H, P] | [G, N] | [G, N]
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(dt_t * A),  A = -exp(A_log)
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] * (x_t[h] outer B_t[group of h]),  S_{-1} = 0
    y_t[h] = S_t[h] C_t[group of h] + D[h] x_t[h]
    g_t  = flatten(y_t) * silu(z_t);  o_t = w_norm * g_t / sqrt(mean(g_t^2) + eps)
    Mixer = o W_out

**Attention mixer**: ``q = u W_q`` (``num_attention_heads`` heads), ``k, v = u
W_k, u W_v`` (``num_key_value_heads``), no bias, NO position embedding;
scores ``q . k * attention_multiplier``; causal softmax; ``W_o``.

**MoE(h)**: ``l = h W_r`` (the router over ALL ``published.num_local_experts``
outputs, float32); ``I = top-k(l)``; ``w = softmax(l[I])``; ``out = sum_{i in I,
held} w_i E_i(h)``, ``E(h) = (silu(h G) * (h U)) D``. Experts ``first_expert ..
first_expert + num_local_experts`` are held; what the others would have added
is left out (another chip's share). **Shared(h)**: the same gated form,
``shared_intermediate_size`` wide, every token.

**Weights** (``make_weights``; the program's recipe leaf for leaf): ``keys =
split(PRNGKey(seed), 12)``; a matmul leaf is ``normal(key) / sqrt(fan_in)``
rounded to ``engine.dtype``, then int8 (int4 levels for the control) with one
float32 scale per output channel. Attention leaves ``[attention layers, in,
out]``: ``wq`` keys[1], ``wk`` keys[2], ``wv`` keys[3], ``wo`` keys[4]. The shared
MLP ``[layers, in, out]``: ``w_gate`` keys[5], ``w_up`` keys[6], ``w_down``
keys[7]. The experts ``[layers, held, in, out]`` from ``fold_in(keys[11], 0 / 1
/ 2)``: ``we_gate``, ``we_up``, ``we_down``. The router keys[9] ``[layers, d,
published experts]`` in ``engine.dtype``. The embedding keys[0] ``*
stand_in.embed_std`` (int8 per row; the head is its transpose). The
state-space leaves from ``sk[i] = fold_in(keys[10], i)``: ``ssm_in [d, d_in + C
+ H]`` and ``ssm_out [d_in, d]`` a layer at a time, layer ``l`` from
``fold_in(sk[0 / 1], l)``; ``ssm_conv_w [ssm layers, K, C]`` ``normal(sk[2]) /
sqrt(K)`` and ``ssm_conv_b`` ``normal(sk[3]) * 0.02`` in ``engine.dtype``;
``ssm_a_log`` ``log(uniform(sk[4]; 1, 16))``; ``ssm_dt_bias`` the inverse softplus
of ``exp(uniform(sk[5]; log 1e-3, log 1e-1))``; ``D`` 1; ``w_norm`` 1 (float32).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

REQUIRED_KEYS = ("hidden_size", "num_hidden_layers", "layer_types", "num_attention_heads", "num_key_value_heads",
                 "intermediate_size", "shared_intermediate_size", "num_local_experts", "num_experts_per_tok",
                 "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
                 "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
                 "residual_multiplier", "attention_multiplier", "logits_scaling", "position_embedding_type",
                 "rms_norm_eps", "tie_word_embeddings", "vocab_size", "first_expert")


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    h, p, n, g = (int(c[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    types = list(c["layer_types"])
    return {
        "d": int(c["hidden_size"]), "layers": int(c["num_hidden_layers"]),
        "ssm": types.count("mamba"), "attn": types.count("attention"),
        "hq": int(c["num_attention_heads"]), "hkv": int(c["num_key_value_heads"]),
        "dh": int(c["hidden_size"]) // int(c["num_attention_heads"]),
        "fe": int(c["intermediate_size"]), "fs": int(c["shared_intermediate_size"]),
        "held": int(c["num_local_experts"]), "first": int(c.get("first_expert", 0)),
        "routed": int(c.get("published", {}).get("num_local_experts", c["num_local_experts"])),
        "top": int(c["num_experts_per_tok"]), "vocab": int(c["vocab_size"]),
        "H": h, "P": p, "N": n, "G": g, "K": int(c["mamba_d_conv"]),
        "d_in": h * p, "C": h * p + 2 * g * n, "in_w": 2 * h * p + 2 * g * n + h,
    }


def expert_layers(c: Dict[str, Any]) -> int:
    """Layers with an expert layer in them: all of them."""
    return int(c["num_hidden_layers"])


def embed_std(c: Dict[str, Any]) -> float:
    """The seeded embedding's standard deviation: the file's ``stand_in``
    group (absent: the recipe every other configuration uses, 0.02)."""
    return float(c.get("stand_in", {}).get("embed_std", 0.02))


def final_norm_gain(c: Dict[str, Any]) -> float:
    """The final norm's gain of the seeded weights (the ``stand_in`` group;
    absent: 1, the recipe's): it multiplies every logit alike."""
    return float(c.get("stand_in", {}).get("final_norm_gain", 1.0))


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ModelConfig``."""
    s = _sizes(c)
    if len(c["layer_types"]) != s["layers"] or s["ssm"] + s["attn"] != s["layers"]:
        raise ValueError("layer_types names every layer 'mamba' or 'attention'")
    if s["d_in"] != int(c["mamba_expand"]) * s["d"]:
        raise ValueError("mamba_n_heads * mamba_d_head is mamba_expand * hidden_size")
    if not c["mamba_conv_bias"] or c["mamba_proj_bias"] or c.get("attention_bias") or not c["tie_word_embeddings"]:
        raise ValueError("the granite_hybrid family has a convolution bias, no projection or attention bias, a tied head")
    if c["position_embedding_type"] not in ("nope", "rope"):
        raise ValueError(f"position_embedding_type {c['position_embedding_type']!r}")
    return dict(
        name=str(c["model"]), vocab_size=s["vocab"], d_model=s["d"], n_layers=s["layers"],
        n_heads=s["hq"], n_kv_heads=s["hkv"], d_head=s["dh"], d_ff=s["fs"],
        rope_theta=float(c.get("rope_theta", 10000.0)), norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]), tie_embeddings=True,
        d_ff_expert=s["fe"], n_experts=s["held"], first_expert=s["first"], router_width=s["routed"],
        top_k_experts=s["top"], renormalize_topk=True,
        layer_types=tuple(c["layer_types"]), ssm_n_heads=s["H"], ssm_d_head=s["P"], ssm_d_state=s["N"],
        ssm_n_groups=s["G"], ssm_d_conv=s["K"], ssm_chunk_size=int(c["mamba_chunk_size"]),
        embedding_multiplier=float(c["embedding_multiplier"]), residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]), logits_scaling=float(c["logits_scaling"]),
        position_embedding="none" if c["position_embedding_type"] == "nope" else "rope",
        init_embed_std=embed_std(c), init_final_norm_gain=final_norm_gain(c),
    )


def _quant(w, axis: int, levels: float):
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=axis, keepdims=True), 1e-8) / levels
    return {"q": jnp.clip(jnp.round(wf / scale), -levels, levels).astype(jnp.int8), "s": scale}


def make_weights(cfg: Dict[str, Any], seed: int, bits: int = 8):
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    s = _sizes(cfg)
    d, layers, la, ls, e = s["d"], s["layers"], s["attn"], s["ssm"], s["held"]
    levels = 127.0 if bits == 8 else 7.0
    stored = jnp.dtype(cfg.get("engine", {}).get("dtype", "bfloat16"))
    plan = (  # leaf, key index, shape, fan-in
        ("wq", 1, (la, d, s["hq"] * s["dh"]), d),
        ("wk", 2, (la, d, s["hkv"] * s["dh"]), d),
        ("wv", 3, (la, d, s["hkv"] * s["dh"]), d),
        ("wo", 4, (la, s["hq"] * s["dh"], d), s["hq"] * s["dh"]),
        ("w_gate", 5, (layers, d, s["fs"]), d),
        ("w_up", 6, (layers, d, s["fs"]), d),
        ("w_down", 7, (layers, s["fs"], d), s["fs"]),
    )
    expert_plan = (("we_gate", (layers, e, d, s["fe"]), d), ("we_up", (layers, e, d, s["fe"]), d),
                   ("we_down", (layers, e, s["fe"], d), s["fe"]))
    proj_plan = (("ssm_in", (d, s["in_w"]), d), ("ssm_out", (s["d_in"], d), s["d_in"]))
    std = embed_std(cfg)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(stored)

        embed = (jax.random.normal(keys[0], (s["vocab"], d), dtype=jnp.float32) * std).astype(stored)
        out = {"embed": _quant(embed, -1, 127.0), "router": mat(keys[9], (layers, d, s["routed"]), d).astype(jnp.float32)}
        for name, ki, shape, fan_in in plan:
            out[name] = _quant(mat(keys[ki], shape, fan_in), -2, levels)
        for fold, (name, shape, fan_in) in enumerate(expert_plan):
            out[name] = _quant(mat(jax.random.fold_in(keys[11], fold), shape, fan_in), -2, levels)
        sk = [jax.random.fold_in(keys[10], i) for i in range(6)]
        for i, (name, shape, fan_in) in enumerate(proj_plan):
            out[name] = jax.lax.map(
                lambda li, pk=sk[i], shape=shape, fan_in=fan_in: _quant(
                    mat(jax.random.fold_in(pk, li), shape, fan_in), -2, levels),
                jnp.arange(ls))
        out["ssm_conv_w"] = mat(sk[2], (ls, s["K"], s["C"]), s["K"]).astype(jnp.float32)
        out["ssm_conv_b"] = (jax.random.normal(sk[3], (ls, s["C"]), dtype=jnp.float32) * 0.02).astype(stored).astype(
            jnp.float32)
        out["ssm_a_log"] = jnp.log(jax.random.uniform(sk[4], (ls, s["H"]), jnp.float32, 1.0, 16.0))
        dt0 = jnp.exp(jax.random.uniform(sk[5], (ls, s["H"]), jnp.float32, math.log(1e-3), math.log(1e-1)))
        out["ssm_dt_bias"] = dt0 + jnp.log(-jnp.expm1(-dt0))
        out["ssm_d"] = jnp.ones((ls, s["H"]), dtype=jnp.float32)
        return out

    return build(jax.random.PRNGKey(seed))


def _deq(leaf):
    return leaf["q"].astype(jnp.float32) * leaf["s"]


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _parts(cfg: Dict[str, Any], length: int):
    """The jitted pieces of one row of ``length`` tokens. The caller walks
    the layers in Python and hands each piece ONE sublayer's weights, so
    that no more than one of them is dequantized at a time."""
    s = _sizes(cfg)
    eps = float(cfg["rms_norm_eps"])
    rm = float(cfg["residual_multiplier"])
    hi = jax.default_matmul_precision("highest")
    H, P, N, G, K, d_in = s["H"], s["P"], s["N"], s["G"], s["K"], s["d_in"]

    @jax.jit
    def embed(emb, tokens):
        return float(cfg["embedding_multiplier"]) * (emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens])

    def ssm(hn, w):
        zxbcdt = hn @ _deq(w["ssm_in"])
        z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : d_in + s["C"]], zxbcdt[:, d_in + s["C"] :]
        before = jnp.concatenate([jnp.zeros((K - 1, s["C"])), xbc], axis=0)  # zeros before the sequence
        c = jax.nn.silu(w["ssm_conv_b"] + sum(w["ssm_conv_w"][k] * before[k : k + length] for k in range(K)))
        x = c[:, :d_in].reshape(length, H, P)
        bm = jnp.repeat(c[:, d_in : d_in + G * N].reshape(length, G, N), H // G, axis=1)  # a head's group
        cm = jnp.repeat(c[:, d_in + G * N :].reshape(length, G, N), H // G, axis=1)
        dt = jax.nn.softplus(dt + w["ssm_dt_bias"])
        a_neg = -jnp.exp(w["ssm_a_log"])

        def token(state, xs):  # the recurrence, one token at a time
            x_t, b_t, c_t, dt_t = xs
            state = (jnp.exp(dt_t * a_neg)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return state, jnp.einsum("hpn,hn->hp", state, c_t) + w["ssm_d"][:, None] * x_t

        _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (x, bm, cm, dt))
        gated = y.reshape(length, d_in) * jax.nn.silu(z)
        return _norm(gated, eps) @ _deq(w["ssm_out"])

    def attention(hn, w):
        q = (hn @ _deq(w["wq"])).reshape(length, s["hq"], s["dh"])
        k = jnp.repeat((hn @ _deq(w["wk"])).reshape(length, s["hkv"], s["dh"]), s["hq"] // s["hkv"], axis=1)
        v = jnp.repeat((hn @ _deq(w["wv"])).reshape(length, s["hkv"], s["dh"]), s["hq"] // s["hkv"], axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * float(cfg["attention_multiplier"])  # no position embedding
        causal = jnp.tril(jnp.ones((length, length), dtype=bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v).reshape(length, s["hq"] * s["dh"]) @ _deq(w["wo"])

    def ffn(hn, w):
        logits = hn @ w["router"]  # all the published outputs
        top_l, top_i = jax.lax.top_k(logits, s["top"])
        chosen = jnp.sum(jax.nn.one_hot(top_i, s["routed"]) * jax.nn.softmax(top_l, axis=-1)[..., None], axis=1)
        combine = chosen[:, s["first"] : s["first"] + s["held"]]  # [T, held]: the others live elsewhere

        def one(acc, xs):  # one expert dequantized at a time
            gate, up, down, weight = xs
            y = (jax.nn.silu(hn @ _deq(gate)) * (hn @ _deq(up))) @ _deq(down)
            return acc + weight[:, None] * y, None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(hn), (w["we_gate"], w["we_up"], w["we_down"], combine.T))
        shared = (jax.nn.silu(hn @ _deq(w["w_gate"])) * (hn @ _deq(w["w_up"]))) @ _deq(w["w_down"])
        return routed + shared

    def sublayer(f):
        @jax.jit
        def run(x, w):
            with hi:
                return x + rm * f(_norm(x, eps), w)

        return run

    @jax.jit
    def head(x, emb):  # x [n_out, d]: the positions asked for; the tied head
        with hi:
            return (final_norm_gain(cfg) * _norm(x, eps) @ _deq(emb).T) / float(cfg["logits_scaling"])

    return embed, sublayer(ssm), sublayer(attention), sublayer(ffn), head


SSM_LEAVES = ("ssm_in", "ssm_out", "ssm_conv_w", "ssm_conv_b", "ssm_a_log", "ssm_dt_bias", "ssm_d")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
FFN_LEAVES = ("w_gate", "w_up", "w_down", "we_gate", "we_up", "we_down", "router")


def _logits(cfg: Dict[str, Any], parts, weights, tokens, first: int, n: int):
    embed, ssm, attention, ffn, head = parts

    def take(names, at):
        return {name: jax.tree_util.tree_map(lambda a: a[at], weights[name]) for name in names}

    x = embed(weights["embed"], tokens)
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(cfg["layer_types"]):
        if kind == "mamba":
            x = ssm(x, take(SSM_LEAVES, seen[kind]))
        else:
            x = attention(x, take(ATTENTION_LEAVES, seen[kind]))
        seen[kind] += 1
        x = ffn(x, take(FFN_LEAVES, layer))
    return head(x[first : first + n], weights["embed"])


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
    """Matmul weights (and the convolution, which rides with its mixer): one
    state-space mixer, its two projections alone, one attention mixer, the
    shared MLP, the router, one expert, the tied head."""
    s = _sizes(cfg)
    d = s["d"]
    proj = d * s["in_w"] + s["d_in"] * d
    return {
        "ssm_proj": proj,
        "ssm": proj + s["K"] * s["C"],
        "attention": 2 * d * s["hq"] * s["dh"] + 2 * d * s["hkv"] * s["dh"],
        "shared": 3 * d * s["fs"],
        "router": d * s["routed"],
        "expert": 3 * d * s["fe"],
        "head": d * s["vocab"],
    }


def outside_experts_params(cfg: Dict[str, Any]) -> int:
    """Everything a step reads whoever is routed where: the mixers, the
    shared MLPs and the routers of every layer."""
    s, p = _sizes(cfg), params(cfg)
    return s["ssm"] * p["ssm"] + s["attn"] * p["attention"] + s["layers"] * (p["shared"] + p["router"])


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """What is stored here: one byte a matmul weight (int8 codes of the
    mixers, the shared MLPs, the held experts, the embedding that is also the
    head; the router and the convolution counted at one byte too: 0.2% of the
    whole); scales, norms and the heads' scalars are left out."""
    s, p = _sizes(cfg), params(cfg)
    return int(outside_experts_params(cfg) + s["layers"] * s["held"] * p["expert"] + p["head"])


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V heads of the ATTENTION layers; a state-space layer caches nothing a token."""
    s = _sizes(cfg)
    return s["attn"] * 2 * s["hkv"] * s["dh"] * itemsize


def state_bytes_per_row(cfg: Dict[str, Any], conv_itemsize: int = 2) -> int:
    """One row's recurrent state over the state-space layers, whatever its
    length: ``S [H, P, N]`` float32 and the convolution's last ``K - 1``
    inputs in the engine's dtype."""
    s = _sizes(cfg)
    return s["ssm"] * (s["H"] * s["P"] * s["N"] * 4 + (s["K"] - 1) * s["C"] * conv_itemsize)


def experts_touched(cfg: Dict[str, Any], rows: float) -> float:
    """Held experts of ONE layer that ``rows`` tokens reach when each takes
    ``num_experts_per_tok`` of the published experts evenly and independently."""
    s = _sizes(cfg)
    return s["held"] * (1.0 - (1.0 - s["top"] / s["routed"]) ** rows)


def expert_bytes(cfg: Dict[str, Any], touched: float) -> float:
    """int8 bytes of ``touched`` routed experts (summed over layers by the caller)."""
    return touched * params(cfg)["expert"]


def ssm_state_bytes(cfg: Dict[str, Any], rows: float) -> float:
    """The recurrent state of ``rows`` rows read and written once, all state-space layers."""
    return 2.0 * rows * state_bytes_per_row(cfg)


def ssm_proj_bytes(cfg: Dict[str, Any]) -> int:
    """int8 bytes of the state-space mixers' two projections, all layers."""
    return _sizes(cfg)["ssm"] * params(cfg)["ssm_proj"]


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float) -> float:
    """Least bytes one decode step moves: every weight outside the routed
    experts once (the tied head among them), of the held experts those that
    ``rows`` tokens touch under even, independent routing, the live rows'
    recurrent state read and written, their cached keys and values, one
    token's written, their embedding rows, the float32 logits."""
    s, p = _sizes(cfg), params(cfg)
    touched = s["layers"] * expert_bytes(cfg, experts_touched(cfg, rows))
    kv = kv_bytes_per_token(cfg)
    return (outside_experts_params(cfg) + p["head"] + touched + ssm_state_bytes(cfg, rows)
            + context_tokens * kv + rows * kv + rows * s["d"] + rows * s["vocab"] * 4)


def _token_matmul_params(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token uses HERE: the mixers, the shared MLPs, the
    routers and its expected share of the held experts."""
    s, p = _sizes(cfg), params(cfg)
    return outside_experts_params(cfg) + s["layers"] * s["top"] * s["held"] / s["routed"] * p["expert"]


def _recurrence_flops(cfg: Dict[str, Any]) -> float:
    """A token through the recurrences: decay, input and read of every state value."""
    s = _sizes(cfg)
    return 6.0 * s["ssm"] * s["d_in"] * s["N"]


def _attention_flops(cfg: Dict[str, Any], context: float) -> float:
    s = _sizes(cfg)
    return 4.0 * s["attn"] * s["hq"] * s["dh"] * context


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    return (2.0 * (_token_matmul_params(cfg) + params(cfg)["head"]) + _recurrence_flops(cfg)
            + _attention_flops(cfg, context))


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """The layers for every token, causal attention (half the square), the head once."""
    return ((2.0 * _token_matmul_params(cfg) + _recurrence_flops(cfg)) * prompt_tokens
            + _attention_flops(cfg, (prompt_tokens + 1) / 2.0) * prompt_tokens + 2.0 * params(cfg)["head"])
