"""Decode roofline microbenchmark: where does a decode step's time go?

Times each per-step component of qwen2:1.5b decode in isolation on the
real chip — raw HBM bandwidth, each weight-matmul shape (bf16 / int8 /
int4-kernel), the logits head, attention, sampling — and prints a JSON
report with a per-step budget so kernel work targets the actual
bottleneck instead of a guess (VERDICT round-1 item 4).

Each op is timed inside one jitted ``lax.fori_loop`` whose carry feeds
the next iteration's input (defeats loop-invariant hoisting and host
dispatch noise).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    quantize_tensor,
    quantize_tensor_int4,
    quantize_tensor_rowwise,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant import (
    int4_matmul,
)

ITERS = 200


def timed_loop(step_fn, x0, iters=ITERS):
    """step_fn: carry -> carry (same shape). Returns seconds per call.

    A single timed call carries the fixed dispatch cost; instead time the
    jitted loop at N and 5N iterations and take the slope — the fixed
    per-dispatch cost cancels.
    """

    @functools.partial(jax.jit, static_argnums=1)
    def run(x, n):
        return lax.fori_loop(0, n, lambda i, c: step_fn(c), x)

    def once(n):
        y = run(x0, n)
        jax.block_until_ready(y)  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x0, n))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = once(iters)
    t5 = once(5 * iters)
    if t5 <= t1:  # noise swamped the slope — the measurement is unusable
        return float("nan")
    return (t5 - t1) / (4 * iters)


def bench_membw():
    a = jnp.ones((1536 * 1024, 1024), dtype=jnp.int8)  # 1.5 GiB

    def step(c):
        return c * 0.0 + jnp.sum(a, dtype=jnp.int32).astype(jnp.float32)

    s = timed_loop(step, jnp.float32(0.0), iters=5)
    return {"bytes": a.nbytes, "s_per_pass": s, "GBps": a.nbytes / s / 1e9}


def _carry_step(f, x):
    """Wrap op f(x_like)->y so output feeds back into a same-shaped carry."""

    def step(c):
        y = f(c)
        # fold y back into an x-shaped carry with a cheap reduction
        return c + jnp.mean(y).astype(c.dtype) * 0.0 + jnp.float32(0).astype(c.dtype)

    return step


def bench_matmul(in_dim, out_dim, key):
    w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * 0.02
    wq8 = quantize_tensor(w)
    wq4 = quantize_tensor_int4(w)
    x = jnp.ones((1, 1, in_dim), dtype=jnp.bfloat16)
    res = {}

    wbf = w.astype(jnp.bfloat16)
    res["bf16"] = timed_loop(
        _carry_step(lambda c: jnp.einsum("bsd,dh->bsh", c, wbf), x), x
    )
    deq8 = lambda c: jnp.einsum(  # noqa: E731
        "bsd,dh->bsh",
        c,
        (wq8["q"].astype(jnp.float32) * wq8["s"]).astype(jnp.bfloat16),
    )
    res["int8_einsum"] = timed_loop(_carry_step(deq8, x), x)

    def k4(c):
        return int4_matmul(c.reshape(1, in_dim), wq4["q4"], wq4["s"]).reshape(
            1, 1, out_dim
        )

    res["int4_kernel"] = timed_loop(_carry_step(k4, x), x)
    res["int8_bytes"] = wq8["q"].nbytes
    res["int4_bytes"] = wq4["q4"].nbytes
    return res


def bench_logits(d=1536, vocab=151_936):
    key = jax.random.PRNGKey(0)
    embed = jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02
    e8 = quantize_tensor_rowwise(embed)
    h = jnp.ones((1, d), dtype=jnp.bfloat16)

    def logits8(c):
        head = (e8["q"].astype(jnp.float32) * e8["s"]).astype(jnp.bfloat16)
        return jnp.einsum(
            "...d,vd->...v", c.astype(jnp.bfloat16), head,
            preferred_element_type=jnp.float32,
        )

    res = {"int8_logits": timed_loop(_carry_step(logits8, h), h)}
    # int8-direct MXU contraction: dot in int-free bf16 without per-row
    # scale fusion is impossible (scales are per-V = per-output), so scale
    # applies to the OUTPUT instead: logits[v] = (x @ q[v,:]) * s[v]
    def logits8_post(c):
        raw = jnp.einsum(
            "...d,vd->...v",
            c.astype(jnp.bfloat16),
            e8["q"].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return raw * e8["s"][:, 0]

    res["int8_logits_postscale"] = timed_loop(_carry_step(logits8_post, h), h)
    res["argmax"] = timed_loop(
        _carry_step(
            lambda c: jnp.argmax(c, axis=-1).astype(jnp.float32)[..., None]
            * jnp.ones((1, vocab), jnp.bfloat16),
            jnp.ones((1, vocab), jnp.bfloat16),
        ),
        jnp.ones((1, vocab), jnp.bfloat16),
    )
    res["embed_bytes"] = e8["q"].nbytes
    return res


def bench_attention(hkv=2, hq=12, dh=128, t=320):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, hq, dh), dtype=jnp.bfloat16)
    kc = jax.random.normal(key, (1, hkv, t, dh), dtype=jnp.bfloat16)
    vc = jax.random.normal(key, (1, hkv, t, dh), dtype=jnp.bfloat16)
    lengths = jnp.asarray([t], dtype=jnp.int32)

    def att(c):
        return pallas_decode_attention(c, kc, vc, lengths)

    return {"decode_attention": timed_loop(_carry_step(att, q), q)}


def main():
    report = {"backend": jax.default_backend()}
    report["membw"] = bench_membw()
    key = jax.random.PRNGKey(0)
    shapes = {
        "wq_wo_1536x1536": (1536, 1536, 2),
        "wk_wv_1536x256": (1536, 256, 2),
        "gate_up_1536x8960": (1536, 8960, 2),
        "down_8960x1536": (8960, 1536, 1),
    }
    report["matmuls"] = {}
    for name, (i, o, count) in shapes.items():
        key, sub = jax.random.split(key)
        report["matmuls"][name] = bench_matmul(i, o, sub)
        report["matmuls"][name]["count_per_layer"] = count
    report["logits"] = bench_logits()
    report["attention"] = bench_attention()

    # per-step budget estimate for qwen2:1.5b (28 layers)
    for mode in ("bf16", "int8_einsum", "int4_kernel"):
        per_layer = sum(
            v[mode] * v["count_per_layer"] for v in report["matmuls"].values()
        )
        report[f"step_estimate_{mode}_ms"] = round(
            1000
            * (
                28 * (per_layer + report["attention"]["decode_attention"])
                + report["logits"]["int8_logits"]
            ),
            3,
        )
    print(json.dumps(report, indent=2, default=float))


if __name__ == "__main__":
    main()
