"""Accelerator memory budget probing and weight-size estimation.

The reference never has to reason about accelerator memory — Ollama
rejects or swaps models on its own. This engine loads weights into HBM
itself, and an oversized model surfaces as an opaque RESOURCE_EXHAUSTED
deep inside XLA, possibly hours into a sweep. ``device_memory_budget``
probes what this process can actually allocate; the engine's
``load_model`` compares it against ``estimate_weight_bytes`` and fails
fast with both numbers and the remedies (quantize harder, shard over a
mesh) in the message.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_OVERRIDE = "TPU_MEMORY_BUDGET_BYTES"
ALLOC_ENV_OVERRIDE = "TPU_ALLOC_BUDGET_BYTES"
# Headroom for a load's transient buffers (the largest full-precision
# leaf — e.g. a 256k-vocab f32 embedding ≈ 3 GiB — lives briefly during
# on-device init+quantize). Charged per load on top of resident weights;
# NOT part of steady-state residency.
LOAD_TRANSIENT_HEADROOM_BYTES = int(3.5 * 1024**3)


def _probed_budget(env_var: str, device=None) -> Optional[int]:
    """``env_var`` when it holds an integer, else the device's
    ``memory_stats()["bytes_limit"]``; None on CPU devices (host RAM is
    not the scarce resource, and tests run there) and on backends that
    report no limit."""
    override = os.environ.get(env_var)
    if override:
        try:
            return int(override)
        except ValueError:
            pass
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    stats = device.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return None


def device_allocation_budget(device=None) -> Optional[int]:
    """Total bytes of accelerator memory this process may keep ALLOCATED
    across all resident models (weights + cached prefixes + a load's
    transient headroom — the LRU eviction's bound), or None when
    unknown. ``TPU_ALLOC_BUDGET_BYTES`` overrides the probe."""
    return _probed_budget(ALLOC_ENV_OVERRIDE, device)


def device_memory_budget(device=None) -> Optional[int]:
    """The accelerator-memory budget resident weights are checked
    against at load (the fail-fast before XLA's RESOURCE_EXHAUSTED), or
    None when unknown. ``TPU_MEMORY_BUDGET_BYTES`` overrides the probe."""
    return _probed_budget(ENV_OVERRIDE, device)


def _stack_weight_terms(cfg, experts: float):
    """The parameter accounting of ALL layers shared by residency
    (:func:`estimate_weight_bytes`) and decode streaming
    (:func:`decode_weight_stream_bytes`) — ONE implementation of the
    quantization byte rules, parameterised only by how many experts
    count (all held, shared ones among them, vs the ones a token
    streams). Each kind of layer (``ModelConfig.ffn_kind``: leading dense
    layers, expert layers) is counted times its own number. Returns
    ``(matmul, matmul_out_channels, norms_biases, float32_params)`` in
    parameter counts; the last are the residual-stream maps, kept
    float32 whatever the mode."""
    d, f, l, n = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.blocks_per_layer
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    # attention + dense FFN of every block, the counted experts, the router
    matmul = cfg.stack_matmul_params(experts)
    if cfg.latent:
        attn_out = (
            cfg.q_lora_rank + hq * dh + cfg.cache_k_width
            + hq * (cfg.qk_nope_head_dim + cfg.v_head_dim) + d
        )
        attn_norms = d + cfg.q_lora_rank + cfg.kv_lora_rank
    else:
        attn_out = hq * dh + 2 * hkv * dh + d
        attn_norms = d
    # layers by mixer (ModelConfig.mixer_kind): attention in all of them,
    # or a state-space mixer (two projections; its convolution, norm gain
    # and three scalars a head stay at full precision) in ``state_layers``
    ls, la = cfg.state_layers, cfg.attention_layers
    # scale entries (one per output channel): the mixer in every layer, a
    # dense FFN and the experts in the layers that have them
    matmul_out_channels = (
        la * n * attn_out
        + ls * (cfg.ssm_in_width + d)
        + cfg.n_dense_ffn_layers * n * (2 * f + d)
        + cfg.n_expert_layers * (2 * cfg.d_expert + d) * experts
    )
    # block norms + final norm
    norms_biases = la * n * (attn_norms + d) + ls * (2 * d + cfg.ssm_small_params) + d
    if cfg.router_bias:
        norms_biases += cfg.n_expert_layers * cfg.router_outputs
    if cfg.qkv_bias:
        norms_biases += la * (hq * dh + 2 * hkv * dh)
    return matmul, matmul_out_channels, norms_biases, cfg.hc_params


def _streamed_experts(cfg) -> float:
    """Experts whose weights one decoded token streams."""
    return cfg.active_experts_per_token if cfg.n_experts else 1


def estimate_weight_bytes(
    cfg, quantize: Optional[str], dtype_bytes: int = 2
) -> int:
    """Estimated HBM bytes of one model's parameters under the engine's
    quantization rules (models/quantize.py): matmul weights at the mode's
    width (int8 = 1 B, int4 = 0.5 B + f32 per-output-channel scales),
    embeddings/lm_head at int8 in every quantized mode, norms and biases
    at full precision.
    """
    d = cfg.d_model
    matmul, matmul_out_channels, norms_biases, f32_params = (
        _stack_weight_terms(cfg, experts=max(1, cfg.experts_held))
    )

    embed_params = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    if quantize is None:
        return dtype_bytes * (
            embed_params + matmul + norms_biases
        ) + 4 * f32_params
    weight_b = 1.0 if quantize == "int8" else 0.5
    # per-row embed scales (f32): the int8 embedding table carries one, and
    # an untied lm_head carries its own (quantize.py stores both)
    embed_scale_rows = cfg.vocab_size * (1 if cfg.tie_embeddings else 2)
    return int(
        embed_params  # int8 in both modes
        + 4 * embed_scale_rows
        + matmul * weight_b
        + 4 * matmul_out_channels  # per-output-channel scales (f32)
        + dtype_bytes * norms_biases
        + 4 * f32_params
    )


def decode_weight_stream_bytes(
    cfg, quantize: Optional[str], dtype_bytes: int = 2
) -> float:
    """HBM bytes of WEIGHTS streamed by one single-row decode step.

    Matches :func:`estimate_weight_bytes`'s quantization rules, with two
    decode-specific differences:

    - the embedding table is read ONCE as the logits head (a full
      ``vocab×d`` stream), never a second time for the input token — that
      is a single-row gather, not a stream;
    - only the experts a token is expected to use of those HELD here are
      streamed per token (``ModelConfig.active_experts_per_token``:
      ``top_k_experts`` when every routed expert is here), matching
      ``flops_per_token``'s active-expert accounting.
    """
    d = cfg.d_model
    matmul, matmul_out_channels, norms_biases, f32_params = (
        _stack_weight_terms(cfg, experts=_streamed_experts(cfg))
    )

    if quantize is None:
        return float(
            dtype_bytes * (cfg.vocab_size * d + matmul + norms_biases)
            + 4 * f32_params
        )
    weight_b = 1.0 if quantize == "int8" else 0.5
    return float(
        cfg.vocab_size * d  # logits head: int8 in every quantized mode
        + 4 * cfg.vocab_size  # its per-row f32 scales
        + matmul * weight_b
        + 4 * matmul_out_channels  # per-output-channel f32 scales
        + dtype_bytes * norms_biases
        + 4 * f32_params
    )


def decode_kv_stream_bytes(
    cfg,
    context_len: int,
    kv_quantize: Optional[str] = None,
    dtype_bytes: int = 2,
) -> float:
    """HBM bytes of KV CACHE read by one single-row decode step at the
    given context (the per-step single-position write is negligible and
    excluded). Kept as the single source of the KV formula — the TP
    roofline needs the weight/KV split because sharding treats them
    differently (KV replicates when heads don't divide the mesh)."""
    kv_b = 1 if kv_quantize == "int8" else dtype_bytes
    # one row per token and attention block, as wide as the config's
    # cache says (K and V heads, or a latent cache's one compressed row)
    kv_bytes = cfg.cache_layers * cfg.kv_values_per_token * context_len * kv_b
    if kv_quantize == "int8":
        leaves = bool(cfg.cache_k_width) + bool(cfg.cache_v_width)
        # per-position f32 scales
        kv_bytes += cfg.cache_layers * leaves * cfg.cache_heads * context_len * 4
    return float(kv_bytes)


def decode_state_stream_bytes(cfg, rows: int = 1, dtype_bytes: int = 2) -> float:
    """HBM bytes of RECURRENT STATE one decode step moves for ``rows``
    rows of a model with state-space layers: each row's state
    (``ModelConfig.state_bytes_per_row``: fixed bytes a ROW, where the KV
    cache is bytes a token) read and written once. 0 for every other
    model. Beside :func:`decode_kv_stream_bytes` it is the other half of
    a step's per-row stream, and admission's per-row term
    (``JaxEngine._state_row_bytes``) is its half."""
    return 2.0 * rows * cfg.state_bytes_per_row(dtype_bytes)


# VPU elementwise ops per PACKED WEIGHT BYTE to turn the quantized
# stream into MXU operands, measured/derived in docs/PERF.md:33-46:
# int4 halves layout ≈ 5 (three i32 sign-extension shifts + two
# converts per nibble pair), int4-i32 ≈ 3 (shl/ashr per plane + one
# convert), int8 ≈ 1 (one i8→bf16 convert per byte). bf16 streams are
# MXU operands already.
VPU_UNPACK_OPS_PER_BYTE = {
    "int8": 1.0,
    "int4": 5.0,
    "int4-i32": 3.0,
}


def decode_vpu_unpack_ops_per_step(cfg, quantize: Optional[str]) -> float:
    """VPU elementwise ops one decode step spends unpacking the quantized
    weight stream (the bytes × per-byte cost above). This is the third
    duty term of the energy model: int4 decode is VPU-BOUND
    (docs/PERF.md — the unpack arithmetic, not HBM, sets its 3.6 ms
    step), so billing it at its ~31% bytes-duty would understate a chip
    whose vector unit is saturated."""
    if quantize is None:
        return 0.0
    ops = VPU_UNPACK_OPS_PER_BYTE.get(quantize)
    if ops is None:
        return 0.0
    # only the matmul weight stream is unpacked in-kernel; scales, norms
    # and the (int8) logits head are charged at the int8 rate
    matmul, _, _, _ = _stack_weight_terms(cfg, experts=_streamed_experts(cfg))
    weight_b = 1.0 if quantize == "int8" else 0.5
    body_bytes = matmul * weight_b
    head_bytes = cfg.vocab_size * cfg.d_model  # int8 in every mode
    return float(body_bytes * ops + head_bytes * 1.0)


def estimate_decode_read_bytes_per_step(
    cfg,
    quantize: Optional[str],
    context_len: int,
    kv_quantize: Optional[str] = None,
    dtype_bytes: int = 2,
) -> float:
    """HBM bytes READ by one single-row decode step (single chip).

    Decode is memory-bound: every step streams the full weight set once
    plus the KV cache up to ``context_len``. This is the bytes term of the
    energy model's bandwidth duty cycle (profilers/tpu.py) and of the TP
    decode-time roofline (parallel/roofline.py).
    """
    return (
        decode_weight_stream_bytes(cfg, quantize, dtype_bytes=dtype_bytes)
        + decode_kv_stream_bytes(
            cfg, context_len, kv_quantize=kv_quantize, dtype_bytes=dtype_bytes
        )
        # a state-space layer's state is read AND written: both ride the
        # same bandwidth (0 for a model without such layers)
        + decode_state_stream_bytes(cfg, 1, dtype_bytes=dtype_bytes)
    )


class ModelMemoryError(RuntimeError):
    """A model's estimated weight bytes exceed the probed device budget."""

    def __init__(self, model: str, estimated: int, budget: int, hint: str) -> None:
        super().__init__(
            f"{model}: estimated weight footprint "
            f"{estimated / 1024**3:.2f} GiB exceeds the device budget "
            f"{budget / 1024**3:.2f} GiB — {hint} "
            f"(override the probed budget with {ENV_OVERRIDE}=<bytes>)"
        )
        self.model = model
        self.estimated = estimated
        self.budget = budget
