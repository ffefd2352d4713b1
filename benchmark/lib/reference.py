"""Plain reference of the served decoder-only transformer.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program made. Weights are made here from the seed by the configuration's
stated recipe (``weights`` group of the configuration file):

- ``jax.random.split(PRNGKey(seed), 12)``; leaf ``i`` draws
  ``normal(keys[i]) / sqrt(fan_in)`` in float32 (embedding: ``* 0.02``),
  rounded to bfloat16;
- matmul weights are then stored as symmetric int8 with one float32 scale
  per (layer, output channel): ``scale = max(|w|, 1e-8) / 127``,
  ``q = clip(round(w / scale), -127, 127)``; the embedding with one scale
  per row, the output head per output channel.

``bits=4`` is the control of the output check: the same recipe with the
matmul weights at 4 bits (``/ 7``, clip to [-7, 7]); embedding and head
stay int8, as the program's own int4 mode keeps them.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def shapes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the reference needs, from the configuration's
    (Hugging Face named) keys."""
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    return {
        "d": d,
        "layers": int(cfg["num_hidden_layers"]),
        "hq": hq,
        "hkv": int(cfg.get("num_key_value_heads", hq)),
        "dh": int(cfg.get("head_dim", d // hq)),
        "ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
    }


def _quant(w: jnp.ndarray, axis: int, levels: float):
    wf = w.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(max_abs, 1e-8) / levels
    q = jnp.clip(jnp.round(wf / scale), -levels, levels).astype(jnp.int8)
    return {"q": q, "s": scale}


def make_weights(cfg: Dict[str, Any], seed: int, bits: int = 8):
    """All weights of the model from ``seed``, in one jitted call, as
    int8 codes with float32 scales (norm gains in float32)."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    s = shapes(cfg)
    d, ff, layers = s["d"], s["ff"], s["layers"]
    hq, hkv, dh, vocab = s["hq"], s["hkv"], s["dh"], s["vocab"]
    levels = 127.0 if bits == 8 else 7.0
    plan = (  # leaf, key index, shape, fan-in
        ("wq", 1, (layers, d, hq * dh), d),
        ("wk", 2, (layers, d, hkv * dh), d),
        ("wv", 3, (layers, d, hkv * dh), d),
        ("wo", 4, (layers, hq * dh, d), hq * dh),
        ("w_gate", 5, (layers, d, ff), d),
        ("w_up", 6, (layers, d, ff), d),
        ("w_down", 7, (layers, ff, d), ff),
    )

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 12)

        def mat(k, shape, fan_in):
            w = jax.random.normal(k, shape, dtype=jnp.float32)
            return (w / math.sqrt(fan_in)).astype(jnp.bfloat16)

        out = {
            "embed": _quant(
                (
                    jax.random.normal(keys[0], (vocab, d), dtype=jnp.float32)
                    * 0.02
                ).astype(jnp.bfloat16),
                -1,
                127.0,
            ),
            "lm_head": _quant(mat(keys[8], (d, vocab), d), -2, 127.0),
        }
        for name, ki, shape, fan_in in plan:
            out[name] = _quant(mat(keys[ki], shape, fan_in), -2, levels)
        return out

    return build(jax.random.PRNGKey(seed))


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(cfg: Dict[str, Any], weights, tokens: jnp.ndarray) -> jnp.ndarray:
    """Float32 logits ``[N, S, vocab]`` of token rows ``[N, S]`` (causal,
    positions from 0; padding after a row's end changes nothing before
    it). Norm gains are 1 by the recipe, so they are left out."""
    s = shapes(cfg)
    hq, hkv, dh = s["hq"], s["hkv"], s["dh"]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    n, length = tokens.shape

    def deq(leaf):
        return leaf["q"].astype(jnp.float32) * leaf["s"]

    @jax.jit
    def run(weights, tokens):
        with jax.default_matmul_precision("highest"):
            emb = weights["embed"]
            x = emb["q"][tokens].astype(jnp.float32) * emb["s"][tokens]
            pos = jnp.arange(length, dtype=jnp.float32)
            freqs = jnp.exp(
                -jnp.log(theta) * jnp.arange(0, dh // 2, dtype=jnp.float32)
                / (dh // 2)
            )
            ang = pos[:, None] * freqs[None, :]
            cos = jnp.cos(ang)[None, :, None, :]
            sin = jnp.sin(ang)[None, :, None, :]
            causal = jnp.tril(jnp.ones((length, length), dtype=bool))

            def layer(x, w):
                h = _rms_norm(x, eps)
                q = (h @ deq(w["wq"])).reshape(n, length, hq, dh)
                k = (h @ deq(w["wk"])).reshape(n, length, hkv, dh)
                v = (h @ deq(w["wv"])).reshape(n, length, hkv, dh)
                q, k = _rope(q, cos, sin), _rope(k, cos, sin)
                k = jnp.repeat(k, hq // hkv, axis=2)
                v = jnp.repeat(v, hq // hkv, axis=2)
                sc = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(dh)
                sc = jnp.where(causal[None, None], sc, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1)
                a = jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(
                    n, length, hq * dh
                )
                x = x + a @ deq(w["wo"])
                h = _rms_norm(x, eps)
                up = jax.nn.silu(h @ deq(w["w_gate"])) * (h @ deq(w["w_up"]))
                return x + up @ deq(w["w_down"]), None

            stacked = {k: weights[k] for k in MATMUL_LEAVES}
            x, _ = jax.lax.scan(layer, x, stacked)
            return _rms_norm(x, eps) @ deq(weights["lm_head"])

    return run(weights, tokens)
