"""Operations and bytes the algorithm needs, from the configuration's
shapes alone. Kept with the benchmark so that no PR that claims a gain
can change what a share of the roofline is a share of.

A decode step of ``rows`` live rows must at least: read every matmul
weight and the output head once (int8: one byte each, plus the float32
scales), read each live row's real context of K and V (bf16), write one
token of K and V per row, and write the float32 logits. Padding, gathered
page-table columns and side caches are the program's choice and are NOT
counted: they are the gap a better path closes.
"""

from __future__ import annotations

from typing import Any, Dict

from .reference import shapes


def matmul_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    s = shapes(cfg)
    d, ff, hq, hkv, dh = s["d"], s["ff"], s["hq"], s["hkv"], s["dh"]
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * ff
    return {
        "per_layer": per_layer,
        "blocks": per_layer * s["layers"],
        "head": d * s["vocab"],
        "embed": d * s["vocab"],
    }


def weight_bytes(cfg: Dict[str, Any], weight_itemsize: int = 1) -> int:
    """Bytes of the stored model: codes plus float32 scales (one per
    layer and output channel; the embedding one per row)."""
    s = shapes(cfg)
    p = matmul_params(cfg)
    d, ff, hq, hkv, dh = s["d"], s["ff"], s["hq"], s["hkv"], s["dh"]
    out_channels = s["layers"] * (hq * dh + 2 * hkv * dh + d + 2 * ff + d)
    scales = 4 * (out_channels + 2 * s["vocab"])
    return (p["blocks"] + p["head"] + p["embed"]) * weight_itemsize + scales


def kv_bytes_per_token(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    s = shapes(cfg)
    return 2 * s["layers"] * s["hkv"] * s["dh"] * kv_itemsize


def decode_step_bytes(
    cfg: Dict[str, Any], rows: float, context_tokens: float,
    weight_itemsize: int = 1, kv_itemsize: int = 2,
) -> float:
    """Least bytes one decode step moves, for ``rows`` live rows whose
    contexts sum to ``context_tokens``."""
    s = shapes(cfg)
    p = matmul_params(cfg)
    weights = (p["blocks"] + p["head"]) * weight_itemsize
    kv = kv_bytes_per_token(cfg, kv_itemsize)
    embed_rows = rows * s["d"] * weight_itemsize
    logits = rows * s["vocab"] * 4
    return weights + embed_rows + context_tokens * kv + rows * kv + logits


def decode_token_flops(cfg: Dict[str, Any], context: float) -> float:
    """Forward FLOPs of one decoded token at the given context: two per
    matmul weight, plus QK^T and PV over the context."""
    s = shapes(cfg)
    p = matmul_params(cfg)
    attn = 4 * s["layers"] * context * s["hq"] * s["dh"]
    return 2.0 * (p["blocks"] + p["head"]) + attn


def prefill_flops(cfg: Dict[str, Any], prompt_tokens: int) -> float:
    """Forward FLOPs of a prompt of ``prompt_tokens``: the blocks for
    every token, causal attention (half the square), the head once."""
    s = shapes(cfg)
    p = matmul_params(cfg)
    attn = 4 * s["layers"] * s["hq"] * s["dh"] * prompt_tokens * (prompt_tokens + 1) / 2
    return 2.0 * p["blocks"] * prompt_tokens + attn + 2.0 * p["head"]
