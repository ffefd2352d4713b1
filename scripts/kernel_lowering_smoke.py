"""Compile-only lowering smoke for every Pallas kernel, on the REAL chip.

Round 5 found `pallas_decode_attention_int8` had NEVER lowered on TPU —
its scales BlockSpec violated Mosaic's tiling rules for every int8-KV
shape — because CPU tests run the kernels in interpret mode (numerics
verified, lowering constraints skipped) and no routine chip run selected
that configuration. This script closes the class of bug: it `.lower()
.compile()`s each kernel at representative shapes (flagship-like GQA and
MQA head layouts, solo and batched widths; the grouped expert FFN at the
benchmark's expert shapes, the state-space step at granite's) WITHOUT
timing anything, so a
Mosaic rejection surfaces as a named failure in seconds-per-kernel
instead of lurking until a user enables the feature.

Run on any TPU-attached host:  python scripts/kernel_lowering_smoke.py
Prints one JSON line per case; exits non-zero if any case fails, and
without a TPU backend (nothing would be exercised).
"""

from __future__ import annotations

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.device import (
        on_tpu,
    )

    if not on_tpu():
        print(
            f"kernel_lowering_smoke: needs a TPU backend, found "
            f"{jax.default_backend()!r} (interpret mode would not "
            f"exercise Mosaic lowering)",
            file=sys.stderr,
        )
        return 2

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
        pallas_decode_attention_int8,
        pallas_prefill_attention,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention,
        pallas_paged_decode_attention_mq_parts,
        pallas_paged_decode_attention_mq_parts_int8,
        pallas_paged_decode_attention_parts,
        pallas_paged_decode_attention_parts_int8,
        pool_page_owners,
        xla_paged_decode_attention_parts,
        xla_paged_decode_attention_parts_int8,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant import (
        int4_matmul,
    )

    f32, bf16, i8, i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
    cases = []

    # (hq, hkv, d): flagship GQA 12/2/128, MQA 8/1/128, padded-head 4/2/96
    heads = [(12, 2, 128), (8, 1, 128), (4, 2, 96)]
    for b in (1, 32, 128):
        for hq, hkv, d in heads:
            t = 384
            q = jnp.zeros((b, hq, d), bf16)
            kc = jnp.zeros((b, hkv, t, d), bf16)
            lengths = jnp.full((b,), t, i32)
            cases.append((
                f"decode b={b} {hq}/{hkv}/{d}",
                lambda q=q, kc=kc, lengths=lengths: pallas_decode_attention(
                    q, kc, kc, lengths
                ),
            ))
            kq = jnp.zeros((b, hkv, t, d), i8)
            ks = jnp.zeros((b, hkv, t), f32)
            cases.append((
                f"decode-int8 b={b} {hq}/{hkv}/{d}",
                lambda q=q, kq=kq, ks=ks, lengths=lengths:
                pallas_decode_attention_int8(q, kq, ks, kq, ks, lengths),
            ))
            dp = -(-d // 128) * 128
            pool = jnp.zeros((8, hkv, 128, dp), bf16)
            table = jnp.zeros((b, 2), i32)
            plens = jnp.full((b,), 130, i32)
            # the legacy paged kernel takes pools at the RAW head dim
            # (it pads internally); the stacked parts kernel requires
            # pre-padded pools
            raw_pool = jnp.zeros((8, hkv, 128, d), bf16)
            cases.append((
                f"paged-decode b={b} {hq}/{hkv}/{d}",
                lambda q=q, raw_pool=raw_pool, table=table, plens=plens:
                pallas_paged_decode_attention(
                    q, raw_pool, raw_pool, table, plens
                ),
            ))
            cases.append((
                f"paged-parts b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool=pool, table=table, plens=plens:
                pallas_paged_decode_attention_parts(
                    q, pool, pool, table, plens
                ),
            ))
            cases.append((
                f"paged-parts-xla b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool=pool, table=table, plens=plens:
                xla_paged_decode_attention_parts(
                    q, pool, pool, table, plens
                ),
            ))
            cases.append((
                f"paged-parts-xla-pool b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool=pool, table=table, plens=plens:
                xla_paged_decode_attention_parts(
                    q, pool, pool, table, plens,
                    owners=pool_page_owners(table, plens, 8, 128),
                ),
            ))
            # int8 page pool (codes + per-position scales): the paged ×
            # kv_quantize composition's kernels — exactly the class of
            # shape the round-5 Mosaic-tiling bug hid in (the scales
            # block layout), so every head layout and width lowers here.
            pool8 = jnp.zeros((8, hkv, 128, dp), i8)
            pscale = jnp.zeros((8, hkv, 128), f32)
            cases.append((
                f"paged-parts-int8 b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool8=pool8, pscale=pscale, table=table,
                plens=plens:
                pallas_paged_decode_attention_parts_int8(
                    q, pool8, pscale, pool8, pscale, table, plens
                ),
            ))
            # the whole-stacked-pool variant folds the layer into the
            # DMA offset — a different BlockSpec rank, lowered separately
            pool8_l = jnp.zeros((2, 8, hkv, 128, dp), i8)
            pscale_l = jnp.zeros((2, 8, hkv, 128), f32)
            cases.append((
                f"paged-parts-int8-stacked b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool8_l=pool8_l, pscale_l=pscale_l,
                table=table, plens=plens:
                pallas_paged_decode_attention_parts_int8(
                    q, pool8_l, pscale_l, pool8_l, pscale_l, table,
                    plens, layer=jnp.int32(1),
                ),
            ))
            cases.append((
                f"paged-parts-xla-int8 b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool8=pool8, pscale=pscale, table=table,
                plens=plens:
                xla_paged_decode_attention_parts_int8(
                    q, pool8, pscale, pool8, pscale, table, plens
                ),
            ))
            cases.append((
                f"paged-parts-xla-pool-int8 b={b} {hq}/{hkv}/{d}",
                lambda q=q, pool8=pool8, pscale=pscale, table=table,
                plens=plens:
                xla_paged_decode_attention_parts_int8(
                    q, pool8, pscale, pool8, pscale, table, plens,
                    owners=pool_page_owners(table, plens, 8, 128),
                ),
            ))
            # multi-query verify kernels (ISSUE 10): the k+1-position
            # query block of the native paged speculative verify, at a
            # serving-realistic k=4 — bf16 + int8, per-layer + stacked.
            # Same chip-pending discipline as the PR-1 paged-int8
            # shapes: interpret-mode CI pins numerics, THIS run pins
            # Mosaic lowering.
            qmq = jnp.zeros((b, 5, hq, d), bf16)
            offs = jnp.full((b,), 130, i32)
            cases.append((
                f"paged-mq-parts b={b} q=5 {hq}/{hkv}/{d}",
                lambda qmq=qmq, pool=pool, table=table, plens=plens,
                offs=offs:
                pallas_paged_decode_attention_mq_parts(
                    qmq, pool, pool, table, plens, offs
                ),
            ))
            cases.append((
                f"paged-mq-parts-int8 b={b} q=5 {hq}/{hkv}/{d}",
                lambda qmq=qmq, pool8=pool8, pscale=pscale, table=table,
                plens=plens, offs=offs:
                pallas_paged_decode_attention_mq_parts_int8(
                    qmq, pool8, pscale, pool8, pscale, table, plens,
                    offs,
                ),
            ))
            pool_l = jnp.zeros((2, 8, hkv, 128, dp), bf16)
            cases.append((
                f"paged-mq-parts-stacked b={b} q=5 {hq}/{hkv}/{d}",
                lambda qmq=qmq, pool_l=pool_l, table=table, plens=plens,
                offs=offs:
                pallas_paged_decode_attention_mq_parts(
                    qmq, pool_l, pool_l, table, plens, offs,
                    layer=jnp.int32(1),
                ),
            ))
            cases.append((
                f"paged-mq-parts-int8-stacked b={b} q=5 {hq}/{hkv}/{d}",
                lambda qmq=qmq, pool8_l=pool8_l, pscale_l=pscale_l,
                table=table, plens=plens, offs=offs:
                pallas_paged_decode_attention_mq_parts_int8(
                    qmq, pool8_l, pscale_l, pool8_l, pscale_l, table,
                    plens, offs, layer=jnp.int32(1),
                ),
            ))
    # prefill flash: [B,S] x cache
    for b, s in ((1, 128), (32, 64)):
        hq, hkv, d = 12, 2, 128
        qp = jnp.zeros((b, s, hq, d), bf16)
        kcp = jnp.zeros((b, hkv, 512, d), bf16)
        cases.append((
            f"prefill b={b} s={s}",
            lambda qp=qp, kcp=kcp: pallas_prefill_attention(
                qp, kcp, kcp, jnp.int32(0)
            ),
        ))
    # the int4 dequant matmul (flagship MLP shape; int8 weights ride
    # XLA's own einsum and need no kernel)
    x1 = jnp.zeros((1, 1536), bf16)
    w4 = jnp.zeros((768, 8960), i8)  # halves-packed [IN/2, OUT]
    s4 = jnp.zeros((1, 8960), f32)
    cases.append(("int4-matmul", lambda: int4_matmul(x1, w4, s4)))

    # the grouped expert FFN at the expert cells' real shapes (PERF.md
    # §4): xing4's decode step and 256-token join chunk, longcat's and
    # granite's decode step and chunk. The leaves are hundreds of MB: shapes, not arrays.
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_moe import (
        grouped_expert_ffn,
    )

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    def expert_leaf(kind, e, n_in, n_out):
        if kind == "bf16":
            return shape((2, e, n_in, n_out), bf16)
        return {
            "q": shape((2, e, n_in, n_out), i8),
            "s": shape((2, e, 1, n_out), f32),
        }

    for label, e, d, f, tokens, rows, blocks in (
        ("xing4 decode", 64, 3584, 1024, 32, 8, 80),
        ("xing4 chunk", 64, 3584, 1024, 256, 16, 128),
        ("longcat decode", 16, 6144, 2048, 32, 8, 64),
        ("longcat chunk", 16, 6144, 2048, 256, 8, 400),
        ("granite decode", 9, 4096, 768, 32, 8, 49),
        ("granite chunk", 9, 4096, 768, 256, 64, 49),
    ):
        for kind in ("int8", "bf16"):
            cases.append((
                f"moe-grouped {kind} {label} {e}x{d}x{f} rows={rows}",
                grouped_expert_ffn,
                shape((tokens, d), bf16),
                expert_leaf(kind, e, d, f), expert_leaf(kind, e, d, f),
                expert_leaf(kind, e, f, d),
                shape((), i32), shape((blocks,), i32), shape((), i32),
                shape((blocks * rows,), i32), shape((blocks * rows,), f32),
            ))

    # the state-space decode step over the granite cell's record (a
    # 4.8-GB shape, not an array): 36 entries of a 32-row bucket
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_ssm import (
        ssm_step_live,
    )

    cases.append((
        "ssm-step granite 36x32x128x64x128",
        ssm_step_live,
        shape((36, 32, 128, 64, 128), f32), shape((), i32), shape((32,), i32),
        shape((), i32), shape((32, 128, 64), f32), shape((32, 1, 128), f32),
        shape((32, 1, 128), f32), shape((32, 128), f32), shape((128,), f32),
        shape((128,), f32),
    ))

    failed = []
    for name, fn, *args in cases:
        try:
            jax.jit(fn).lower(*args).compile()
            print(json.dumps({"kernel": name, "lowering": "ok"}), flush=True)
        except Exception as e:  # noqa: BLE001 — report and continue
            msg = f"{type(e).__name__}: {str(e).splitlines()[0][:160]}"
            failed.append(name)
            print(
                json.dumps({"kernel": name, "lowering": "FAIL", "error": msg}),
                flush=True,
            )
            if os.environ.get("SMOKE_VERBOSE"):
                traceback.print_exc()
    print(
        json.dumps(
            {"total": len(cases), "failed": failed or None}
        ),
        flush=True,
    )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
