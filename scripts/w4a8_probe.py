"""Probe: can Mosaic do int8xint8->int32 MXU dots, and does a w4a8 int4
kernel (fewer VPU ops/byte) beat the bf16-dot int4 kernel?

RESULT (2026-07-30, libtpu 0.0.34 / this jax stack): NO — Mosaic does not
legalize `arith.shli` or `arith.muli` on i8 vectors (it lays i8 out
4-per-lane, `vector<8x128x4xi8>`, but only a sparse op set is lowered), so
a narrow-int unpack is not expressible and the int4 kernel's floor is the
int32-shift unpack (~5 VPU ops per packed byte ≈ 3.3 ms/step on
qwen2:1.5b — VPU-bound, matching measurement). Kept as the reproduction
script for when Mosaic grows i8 elementwise support; see
ops/pallas_quant.py for the shipping kernel.

Times one decode-shaped matmul (1536 -> 8960, the MLP gate shape) via the
slope method (N vs 5N fori_loop iterations cancels the fixed dispatch
cost). Prints JSON per variant as it completes.
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    quantize_tensor_int4,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant import (
    int4_matmul,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.device import on_tpu

M = 8


def _w4a8_kernel(
    xq_ref,  # VMEM [8, 2*in_half] int8 (pre-quantized activations)
    p_ref,  # VMEM [block_k, block_n] int8 packed
    s_ref,  # VMEM [1, block_n] f32 weight scales
    sx_ref,  # VMEM [8, 1] f32 activation scales (actually [8,128] padded)
    o_ref,  # VMEM [8, block_n] f32
    acc_ref,  # VMEM [8, block_n] int32
    *,
    block_k: int,
    in_half: int,
    n_k_blocks: int,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = p_ref[...]
    # Shift-free nibble unpack in int8 (Mosaic packs i8 4-per-lane; shifts
    # don't legalize but and/mul/sub do): p = 16*hi + lo_u (no overflow for
    # nibbles in [-7,7]); lo_u = p & 15; signed lo = lo_u - 2*(lo_u & 8).
    lo_u = jnp.bitwise_and(p, jnp.int8(15))
    lo = lo_u - jnp.int8(2) * jnp.bitwise_and(lo_u, jnp.int8(8))
    hi = (p - lo_u) // jnp.int8(16)
    xl = xq_ref[:, pl.ds(k * block_k, block_k)]
    xh = xq_ref[:, pl.ds(in_half + k * block_k, block_k)]
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        xl, lo, dims, preferred_element_type=jnp.int32
    ) + lax.dot_general(xh, hi, dims, preferred_element_type=jnp.int32)

    @pl.when(k == n_k_blocks - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32)
            * s_ref[...]
            * sx_ref[:, :1]
        )


def w4a8_matmul(x, packed, scale):
    m, in_dim = x.shape
    in_half, out_dim = packed.shape
    # per-row activation quantization
    sx = jnp.max(jnp.abs(x), axis=1, keepdims=True).astype(jnp.float32) / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    block_k = 0
    for cand in range(128 * (min(1024, in_half) // 128), 127, -128):
        if in_half % cand == 0:
            block_k = cand
            break
    assert block_k, in_half
    n_k = in_half // block_k
    block_n = 512
    sx_pad = jnp.broadcast_to(sx, (m, 128))
    kernel = functools.partial(
        _w4a8_kernel, block_k=block_k, in_half=in_half, n_k_blocks=n_k
    )
    return pl.pallas_call(
        kernel,
        grid=(-(-out_dim // block_n), n_k),
        in_specs=[
            pl.BlockSpec((M, 2 * in_half), lambda o, k: (0, 0)),
            pl.BlockSpec((block_k, block_n), lambda o, k: (k, o)),
            pl.BlockSpec((1, block_n), lambda o, k: (0, o)),
            pl.BlockSpec((M, 128), lambda o, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda o, k: (0, o)),
        out_shape=jax.ShapeDtypeStruct((M, out_dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M, block_n), jnp.int32)],
        interpret=not on_tpu(),
    )(xq, packed, scale.astype(jnp.float32), sx_pad)


def slope_time(fn, x0, iters=100):
    @functools.partial(jax.jit, static_argnums=1)
    def run(x, n):
        return lax.fori_loop(0, n, lambda i, c: fn(c), x)

    def once(n):
        jax.block_until_ready(run(x0, n))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x0, n))
            best = min(best, time.perf_counter() - t0)
        return best

    t1 = once(iters)
    t5 = once(5 * iters)
    return (t5 - t1) / (4 * iters)


def main():
    in_dim, out_dim = 1536, 8960
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * 0.05
    leaf = quantize_tensor_int4(w)
    x = jax.random.normal(key, (M, in_dim), jnp.bfloat16)

    # correctness of w4a8 vs dequant reference
    ref = (x.astype(jnp.float32) @ (w * 0)).astype(jnp.float32)  # placeholder
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        maybe_dequant,
    )

    want = x.astype(jnp.float32) @ maybe_dequant(leaf, jnp.float32)
    got = w4a8_matmul(x, leaf["q4"], leaf["s"])
    err = float(
        jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9)
    )
    print(json.dumps({"w4a8_rel_err": round(err, 5)}), flush=True)

    def via_bf16(c):
        y = int4_matmul(c, leaf["q4"], leaf["s"])
        return c + jnp.mean(y).astype(c.dtype) * 0

    def via_w4a8(c):
        y = w4a8_matmul(c, leaf["q4"], leaf["s"])
        return c + jnp.mean(y).astype(c.dtype) * 0

    for name, fn in (("int4_bf16_kernel", via_bf16), ("w4a8_kernel", via_w4a8)):
        s = slope_time(fn, x)
        print(
            json.dumps({name: {"us_per_call": round(s * 1e6, 2)}}), flush=True
        )


if __name__ == "__main__":
    main()
