"""A fixture family, no benchmark configuration and no public model: the
expert layer the program already runs (``ModelConfig.n_experts`` /
``top_k_experts``, Mixtral-style). ``test_benchmark_family.py`` copies this
file into a scratch benchmark as ``benchmark/families/fixture_experts.py``,
beside a configuration that names it and a cell, and edits no file that was
there: the proof that a later PR can bring a model as new files only.

Attention is the dense decoder's; the FFN of every layer is ``num_experts``
gated experts of width ``expert_ffn_size`` behind a float32 softmax router
that takes the top ``experts_per_token`` and renormalises their weights.
Weights by the program's ``init_params`` recipe: the dense leaves from the
same keys with an expert axis after the layer axis on ``w_gate``, ``w_up``,
``w_down`` (scales per layer, expert and output channel), the router from
``keys[9]`` and not quantized; every leaf rounded to ``engine.dtype`` before
it is stored, as the program rounds it.

The test's configuration runs in float32. In bfloat16 the program's router
sees activations that differ from the reference's in the third digit, a near
tie between the second and the third expert can go the other way, and every
later position reads that through attention: on the CPU one position in ten
(76-94 of 768, two seeds) had a logit off by 0.1 or more, and the widest gap
of a dry run read 0.24-0.54 in six of a dozen runs (0.006-0.04 in the
others) against the limit 0.5. In float32 no position of those was off by
more than 0.013. A top-k model's cell on the chip has to set its limit from
such readings.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..lib.reference import _quant, _rms_norm, _rope

REQUIRED_KEYS = ("hidden_size", "expert_ffn_size", "num_experts", "experts_per_token", "num_hidden_layers",
                 "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps",
                 "rope_theta")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _sizes(c: Dict[str, Any]) -> Dict[str, int]:
    return {
        "d": int(c["hidden_size"]), "layers": int(c["num_hidden_layers"]), "hq": int(c["num_attention_heads"]),
        "hkv": int(c["num_key_value_heads"]), "dh": int(c["head_dim"]), "ff": int(c["expert_ffn_size"]),
        "experts": int(c["num_experts"]), "top": int(c["experts_per_token"]), "vocab": int(c["vocab_size"]),
    }


def program_config(c: Dict[str, Any]) -> Dict[str, Any]:
    s = _sizes(c)
    return dict(
        name=str(c["model"]), vocab_size=s["vocab"], d_model=s["d"], n_layers=s["layers"], n_heads=s["hq"],
        n_kv_heads=s["hkv"], d_head=s["dh"], d_ff=s["ff"], n_experts=s["experts"], top_k_experts=s["top"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
    )


def make_weights(cfg: Dict[str, Any], seed: int, bits: int = 8):
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    s = _sizes(cfg)
    d, ff, layers, e = s["d"], s["ff"], s["layers"], s["experts"]
    hq, hkv, dh, vocab = s["hq"], s["hkv"], s["dh"], s["vocab"]
    levels = 127.0 if bits == 8 else 7.0
    stored = jnp.dtype(cfg.get("engine", {}).get("dtype", "bfloat16"))
    plan = (  # leaf, key index, shape, fan-in
        ("wq", 1, (layers, d, hq * dh), d),
        ("wk", 2, (layers, d, hkv * dh), d),
        ("wv", 3, (layers, d, hkv * dh), d),
        ("wo", 4, (layers, hq * dh, d), hq * dh),
        ("w_gate", 5, (layers, e, d, ff), d),
        ("w_up", 6, (layers, e, d, ff), d),
        ("w_down", 7, (layers, e, ff, d), ff),
    )

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(stored)

        embed = (jax.random.normal(keys[0], (vocab, d), dtype=jnp.float32) * 0.02).astype(stored)
        out = {
            "embed": _quant(embed, -1, 127.0),
            "lm_head": _quant(mat(keys[8], (d, vocab), d), -2, 127.0),
            "router": mat(keys[9], (layers, d, e), d).astype(jnp.float32),
        }
        for name, ki, shape, fan_in in plan:
            out[name] = _quant(mat(keys[ki], shape, fan_in), -2, levels)
        return out

    return build(jax.random.PRNGKey(seed))


def _forward(cfg: Dict[str, Any], length: int):
    """The jitted forward pass of one row of ``length`` tokens: float32 logits ``[length, vocab]``."""
    s = _sizes(cfg)
    hq, hkv, dh, top = s["hq"], s["hkv"], s["dh"], s["top"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])

    def deq(leaf):
        return leaf["q"].astype(jnp.float32) * leaf["s"]

    @jax.jit
    def run(weights, tokens):
        with jax.default_matmul_precision("highest"):
            emb = weights["embed"]
            x = emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens]
            freqs = jnp.exp(-jnp.log(theta) * jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))
            ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs[None, :]
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            causal = jnp.tril(jnp.ones((length, length), dtype=bool))

            def layer(x, w):
                h = _rms_norm(x, eps)
                q = _rope((h @ deq(w["wq"])).reshape(length, hq, dh), cos, sin)
                k = _rope((h @ deq(w["wk"])).reshape(length, hkv, dh), cos, sin)
                v = (h @ deq(w["wv"])).reshape(length, hkv, dh)
                k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
                sc = jnp.where(causal[None], jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh), -jnp.inf)
                a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v).reshape(length, hq * dh)
                x = x + a @ deq(w["wo"])
                h = _rms_norm(x, eps)
                probs = jax.nn.softmax(h @ w["router"], axis=-1)
                top_w, top_i = jax.lax.top_k(probs, top)
                top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
                combine = jnp.sum(jax.nn.one_hot(top_i, probs.shape[-1]) * top_w[..., None], axis=-2)
                gate = jax.nn.silu(jnp.einsum("sd,edf->sef", h, deq(w["w_gate"])))
                up = jnp.einsum("sd,edf->sef", h, deq(w["w_up"]))
                y = jnp.einsum("sef,efd->sed", gate * up, deq(w["w_down"]))
                return x + jnp.einsum("se,sed->sd", combine, y), None

            stacked = {k: weights[k] for k in ATTENTION_LEAVES + EXPERT_LEAVES + ("router",)}
            x, _ = jax.lax.scan(layer, x, stacked)
            return _rms_norm(x, eps) @ deq(weights["lm_head"])

    return run


def served_logits(cfg: Dict[str, Any], weights, token_rows: Sequence[List[int]],
                  spans: Sequence[Tuple[int, int]]):
    """One request at a time, each padded to a multiple of 128 of its own
    (one compiled pass for each padded length)."""
    forward, out = {}, []
    for row, (first, n) in zip(token_rows, spans):
        toks = np.zeros(-(-len(row) // 128) * 128, dtype=np.int32)
        toks[: len(row)] = row
        if len(toks) not in forward:
            forward[len(toks)] = _forward(cfg, len(toks))
        out.append(forward[len(toks)](weights, jnp.asarray(toks))[first : first + n])
    return out


def _params(cfg: Dict[str, Any]) -> Dict[str, int]:
    s = _sizes(cfg)
    d, hq, hkv, dh = s["d"], s["hq"], s["hkv"], s["dh"]
    return {
        "attention": s["layers"] * (d * hq * dh + 2 * d * hkv * dh + hq * dh * d),
        "router": s["layers"] * d * s["experts"],
        "expert": 3 * d * s["ff"],  # one expert of one layer
        "head": d * s["vocab"],
    }


def weight_bytes(cfg: Dict[str, Any]) -> int:
    """int8 codes (the router in bfloat16); the float32 scales are left out."""
    s, p = _sizes(cfg), _params(cfg)
    return p["attention"] + 2 * p["router"] + s["layers"] * s["experts"] * p["expert"] + 2 * p["head"]


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    s = _sizes(cfg)
    return 2 * s["layers"] * s["hkv"] * s["dh"] * 2


def experts_touched(cfg: Dict[str, Any], rows: float) -> float:
    """Experts of a layer that ``rows`` tokens reach when each takes ``top``
    of ``experts`` evenly: the least that have to be read."""
    s = _sizes(cfg)
    return s["experts"] * (1.0 - (1.0 - s["top"] / s["experts"]) ** rows)


def decode_step_bytes(cfg: Dict[str, Any], rows: float, context_tokens: float) -> float:
    """Attention, router and head once, the experts that the rows touch,
    each live row's context of K and V, one token written a row, the logits."""
    s, p = _sizes(cfg), _params(cfg)
    experts = s["layers"] * experts_touched(cfg, rows) * p["expert"]
    kv = kv_bytes_per_token(cfg)
    return (p["attention"] + 2 * p["router"] + experts + p["head"] + rows * s["d"]
            + context_tokens * kv + rows * kv + rows * s["vocab"] * 4)


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    s, p = _sizes(cfg), _params(cfg)
    active = p["attention"] + p["router"] + s["layers"] * s["top"] * p["expert"] + p["head"]
    return 2.0 * active + 4 * s["layers"] * context * s["hq"] * s["dh"]


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    s, p = _sizes(cfg), _params(cfg)
    blocks = p["attention"] + p["router"] + s["layers"] * s["top"] * p["expert"]
    attn = 4 * s["layers"] * s["hq"] * s["dh"] * prompt_tokens * (prompt_tokens + 1) / 2
    return 2.0 * blocks * prompt_tokens + attn + 2.0 * p["head"]
