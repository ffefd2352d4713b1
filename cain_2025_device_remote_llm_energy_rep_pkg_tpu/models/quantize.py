"""Weight-only quantization: int8 and packed-int4, per-output-channel scales.

TPU reasons: (1) decode is HBM-bandwidth-bound — int8 weights halve and
int4 quarter the bytes every decode step streams, so the bandwidth ceiling
on tokens/s rises accordingly; (2) llama3.1:8b at bf16 (~16 GB) does not
fit a 16 GB v5e chip with cache + activations; at int8 (~8 GB) or int4
(~4 GB) it does. Compute stays bf16/f32: XLA fuses the dequant (int8 →
scale-multiply, int4 → nibble shifts + scale) into the consuming matmul,
so only the HBM read shrinks.

The reference's baseline models are Ollama defaults — 4-bit GGUF quants
(Q4_0/Q4_K) — so 4-bit serving is the apples-to-apples configuration for
the energy comparison, not an extra trick.

Quantized leaves are dicts:
  int8: ``{"q":  int8[..., in,   out], "s": f32[..., 1, out]}``
  int4: ``{"q4": int8[..., in/2, out], "s": f32[..., 1, out]}`` — two
        nibbles per byte packed along the input-feature axis as *halves*:
        packed row i carries weight row i (low nibble) and row i + in/2
        (high nibble), symmetric in [-7, 7]. Halves rather than even/odd
        interleave so the Pallas kernel's unpack needs no cross-lane
        shuffle. (jnp.int4 storage exists but cannot cross the jit
        boundary on this TPU stack, so the packing is explicit int8.)

Performance note (measured on a v5e chip, qwen2:1.5b decode): bf16 203
tok/s → int8 325 tok/s (XLA fuses the int8→bf16 scale-multiply into the
matmul, so the HBM read genuinely halves). int4 through plain XLA does
NOT fuse the nibble unpack (weights materialise per step, ~40 tok/s);
decode-shaped int4 matmuls therefore route through the Pallas kernel in
``ops/pallas_quant.py`` (unpack in VMEM after the packed DMA) → 279
tok/s with bf16 MXU dots and divisor-aligned k-blocks (was 233 with f32
dots + per-block tail masking). int4 remains VPU-bound on the nibble
expansion (~5 VPU ops per packed byte ≈ 3.3 ms/step — arithmetic and
measurement agree); a narrower unpack needs i8 elementwise ops Mosaic
does not yet legalize (scripts/w4a8_probe.py records the attempt), so
int4's role is *capacity* — llama3.1:8b-class models on one 16 GB chip
(int8 ~8.6 GB, int4 ~4.8 GB incl. int8 embeddings) — while int8 is the
speed mode. Resident models are bounded by the chip's ``bytes_limit``
through the engine's LRU weight eviction (utils/memory.py); tensor
parallelism (parallel/tp.py) scales beyond one chip.

Embeddings (and an untied lm_head) quantize at int8 in BOTH modes — the
gather and the logits matmul read them every step and they are a large
fraction of small models' bytes — but never int4 (quality-sensitive, and
a packed gather would straddle row pairs). ``maybe_dequant`` is the single
accessor the model uses, so every weight site transparently takes any
form.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, Union

import jax
import jax.numpy as jnp

QuantLeaf = Dict[str, jnp.ndarray]

# The matmul weights worth quantizing ([L, in, out]-shaped); norms and
# biases stay high-precision.
DEFAULT_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# ... and those of the layers that are no plain decoder's
# (models/transformer.py): the latent projections, the experts beside a
# dense FFN or after a dense prefix, with a block or expert axis before
# ``in`` (scales are per output channel of each), and the shared expert.
# An expert layer's router is never quantized, nor are the residual-stream
# maps (``hc_*``, float32); of a state-space mixer (models/ssm.py) the two
# projections are, its convolution, ``A_log``, ``D``, ``dt_bias`` and norm not;
# of a KDA mixer (models/kda.py) the four projections likewise, and a
# lightning indexer's three.
QUANT_KEYS = DEFAULT_QUANT_KEYS + (
    "w_qa", "w_qb", "w_kva", "w_kvb", "we_gate", "we_up", "we_down",
    "ws_gate", "ws_up", "ws_down", "ssm_in", "ssm_out",
    "kda_in", "kda_fb", "kda_gb", "kda_out", "idx_wq", "idx_wk", "idx_ww",
)
# Quantized at int8 in every mode (see module docstring).
EMBED_KEYS = ("embed", "lm_head")


@jax.jit
def quantize_tensor(w: jnp.ndarray) -> QuantLeaf:
    """Symmetric int8 quantization, scales per output channel.

    The input-feature axis is ``-2`` for both stacked-layer ``[L, in, out]``
    and flat ``[in, out]`` weights, so reducing over exactly that axis keeps
    per-(layer, out-channel) scales — the leading L axis survives, which the
    layer ``lax.scan`` requires of every stacked leaf. Jitted so the f32
    upcast fuses instead of materialising a full-precision copy — the
    streaming big-model load path depends on that."""
    wf = w.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(max_abs, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


@jax.jit
def quantize_tensor_rowwise(w: jnp.ndarray) -> QuantLeaf:
    """Symmetric int8 with one scale per *row* (reduce axis -1) — the right
    scheme for embedding tables [V, D]: each vocab row keeps its own
    resolution (a single outlier row cannot crush the rest), the gather
    dequantizes row-local, and for tied embeddings the logits matmul
    contracts over D so per-V scales are per-output-channel there too."""
    wf = w.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(wf), axis=-1, keepdims=True)
    scale = jnp.maximum(max_abs, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


@jax.jit
def quantize_tensor_int4(w: jnp.ndarray) -> QuantLeaf:
    """Symmetric 4-bit quantization in [-7, 7], the input-feature axis
    (which must be even) packed as halves: low nibbles = first half's
    rows, high nibbles = second half's."""
    if w.shape[-2] % 2 != 0:
        raise ValueError(
            f"int4 packing needs an even input-feature dim, got {w.shape}"
        )
    half = w.shape[-2] // 2
    wf = w.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(max_abs, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int8)
    lo = q[..., :half, :]
    hi = q[..., half:, :]
    packed = ((lo & 0xF) | (hi << 4)).astype(jnp.int8)
    return {"q4": packed, "s": scale}


@jax.jit
def quantize_tensor_int4_i32(w: jnp.ndarray) -> QuantLeaf:
    """Symmetric 4-bit quantization packed EIGHT k-consecutive nibbles per
    int32 lane: ``{"q32": int32 [..., in/8, out], "s": f32 [..., 1, out]}``.

    Alternative layout to :func:`quantize_tensor_int4` (halves-packed
    int8): the kernel loads native i32 vectors, so the unpack is pure
    i32 shift arithmetic — no i8→i32 convert, no 4-per-lane → 1-per-lane
    Mosaic relayout. Nibble p of a lane holds weight row ``8k + p``
    (little-endian); sign is recovered with a shl/ashr pair per plane.
    """
    if w.shape[-2] % 8 != 0:
        raise ValueError(
            f"i32 nibble packing needs in-dim divisible by 8, got {w.shape}"
        )
    wf = w.astype(jnp.float32)
    max_abs = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(max_abs, 1e-8) / 7.0
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int32)
    k8 = w.shape[-2] // 8
    # [..., in, out] → [..., in/8, 8, out]; combine nibbles little-endian
    qg = q.reshape(*q.shape[:-2], k8, 8, q.shape[-1])
    packed = jnp.zeros(qg.shape[:-2] + (qg.shape[-1],), jnp.int32)
    for p in range(8):
        packed = packed | ((qg[..., p, :] & 0xF) << (4 * p))
    return {"q32": packed, "s": scale}


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and set(leaf) in (
        {"q", "s"}, {"q4", "s"}, {"q32", "s"},
    )


def maybe_dequant(
    leaf: Union[jnp.ndarray, QuantLeaf], dtype=jnp.bfloat16
) -> jnp.ndarray:
    """Dequantize a quantized leaf (or pass a plain array through)."""
    if not is_quantized(leaf):
        return leaf
    if "q4" in leaf:
        packed = leaf["q4"]
        # arithmetic shifts sign-extend int8, recovering the signed nibbles
        lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
        hi = jnp.right_shift(packed, 4)
        q = jnp.concatenate([lo, hi], axis=-2)  # halves layout
    elif "q32" in leaf:
        packed = leaf["q32"]  # [..., in/8, out] int32, 8 nibbles per lane
        planes = [
            jnp.right_shift(jnp.left_shift(packed, 28 - 4 * p), 28)
            for p in range(8)
        ]
        q = jnp.stack(planes, axis=-2).reshape(
            *packed.shape[:-2], packed.shape[-2] * 8, packed.shape[-1]
        )
    else:
        q = leaf["q"]
    return (q.astype(jnp.float32) * leaf["s"]).astype(dtype)


# The Pallas kernels on the weights (the int4 matmul, the grouped expert
# FFN of ops/pallas_moe.py) have no GSPMD partitioning rule: under a mesh
# the int4 kernel would force the partitioner to replicate (all-gather)
# the packed weights every step — the opposite of what sharding them is
# for — and real chips refuse to partition a Mosaic kernel at all. Sharded
# engines disable them for their traces via this flag (the XLA paths
# partition fine).
_UNPARTITIONED_KERNELS = contextvars.ContextVar(
    "unpartitioned_kernels_enabled", default=True
)


def unpartitioned_kernels_enabled() -> bool:
    """False inside a sharded engine's traces."""
    return _UNPARTITIONED_KERNELS.get()


@contextlib.contextmanager
def unpartitioned_kernels_disabled():
    token = _UNPARTITIONED_KERNELS.set(False)
    try:
        yield
    finally:
        _UNPARTITIONED_KERNELS.reset(token)


def dense_dot(x: jnp.ndarray, leaf: Union[jnp.ndarray, QuantLeaf]) -> jnp.ndarray:
    """``x [B,S,IN] @ weight [IN,OUT]`` for any leaf form.

    Decode-shaped int4 matmuls (B·S ≤ 8 rows, tile-compatible dims) route
    through the Pallas kernels so the packed bytes cross HBM packed;
    everything else uses the einsum with XLA-fused dequant (a no-op for
    plain tensors)."""
    if (
        is_quantized(leaf)
        and "q4" in leaf
        and leaf["q4"].ndim == 2
        and unpartitioned_kernels_enabled()
    ):
        from ..ops.pallas_quant import int4_matmul, int4_matmul_supported

        b, s, d = x.shape
        in_half, out_dim = leaf["q4"].shape
        if int4_matmul_supported(b * s, in_half, out_dim):
            out = int4_matmul(x.reshape(b * s, d), leaf["q4"], leaf["s"])
            return out.reshape(b, s, out_dim)
    if (
        is_quantized(leaf)
        and "q32" in leaf
        and leaf["q32"].ndim == 2
        and unpartitioned_kernels_enabled()
    ):
        from ..ops.pallas_quant import MAX_KERNEL_ROWS, int4_matmul_i32

        b, s, d = x.shape
        k8, out_dim = leaf["q32"].shape
        # non-128-multiple k8 is allowed: the kernel zero-pads the packed
        # rows (a per-call copy — see docs/PERF.md's measured verdict)
        if b * s <= MAX_KERNEL_ROWS and out_dim % 128 == 0:
            out = int4_matmul_i32(x.reshape(b * s, d), leaf["q32"], leaf["s"])
            return out.reshape(b, s, out_dim)
    return jnp.einsum("bsd,dh->bsh", x, maybe_dequant(leaf, x.dtype))


def embed_lookup(
    leaf: Union[jnp.ndarray, QuantLeaf], tokens: jnp.ndarray, dtype
) -> jnp.ndarray:
    """Row-gather from a (possibly int8-quantized) embedding table without
    materialising the dequantized table."""
    if is_quantized(leaf):
        rows = leaf["q"][tokens].astype(jnp.float32)
        if leaf["s"].shape[-1] == 1:  # per-row scales [V, 1]
            rows = rows * leaf["s"][tokens]
        else:  # per-column scales [1, D]
            rows = rows * leaf["s"][0]
        return rows.astype(dtype)
    return leaf[tokens]


def quantize_leaf(
    name: str, leaf: Any, mode: str = "int8", keys=QUANT_KEYS
) -> Any:
    """The per-leaf quantization rule: named matmul weights at ``mode``,
    embeddings at int8 (per-row scales), untied lm_head at int8
    (per-output-channel), everything else passes through. ``int4-i32``
    is the experimental i32-lane nibble layout (scripts/int4_i32_bench.py
    decides whether it replaces the halves layout)."""
    if mode not in ("int8", "int4", "int4-i32"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    if is_quantized(leaf):
        return leaf
    stem, _, block = name.rpartition("_")
    if stem and block.isdigit():
        name = stem  # block j's leaf of a layer of several: <leaf>_<j>
    if name in keys:
        qt = {
            "int8": quantize_tensor,
            "int4": quantize_tensor_int4,
            "int4-i32": quantize_tensor_int4_i32,
        }[mode]
        return qt(leaf)
    if name == "embed":
        # [V, D] with per-row scales (see quantize_tensor_rowwise)
        return quantize_tensor_rowwise(leaf)
    if name == "lm_head":
        # [D, V]: axis -2 reduce is already per-output-channel
        return quantize_tensor(leaf)
    return leaf


def quantize_params(
    params: Dict[str, Any], keys=QUANT_KEYS, mode: str = "int8"
) -> Dict[str, Any]:
    """Quantize a whole parameter dict via :func:`quantize_leaf`."""
    return {
        name: quantize_leaf(name, leaf, mode, keys)
        for name, leaf in params.items()
    }


# -- KV-cache quantization ----------------------------------------------------
# Decode streams the whole cache every step; for many-KV-head models
# (phi3: 32 full-width heads → ~0.8 GB/step at 2 k context) the cache
# rivals the weight bytes. int8 with one scale per (…, position) vector
# halves that stream; the decode kernel dequantizes K by scaling scores
# and V by scaling probabilities — two cheap per-position multiplies.

# int8 codes span -127..127: the divisor of every KV scale
KV_INT8_LEVELS = 127.0


def quantize_kv_cache(
    k_cache: jnp.ndarray, v_cache: jnp.ndarray, levels=KV_INT8_LEVELS
):
    """bf16 cache ``[..., T, D]`` → ``{"q": int8 [..., T, D], "s": f32
    [..., T]}`` with symmetric per-vector scales. Unwritten (zero)
    positions get the epsilon scale and zero codes — masked by position
    in attention anyway. ``levels``: see :func:`quantize_kv_vector`."""

    def one(c):
        # single source of the scale math — decode-step writes must stay
        # numerically identical to this bulk quantization for the
        # kernel-parity guarantee to hold
        q, s = quantize_kv_vector(c, levels)
        return {"q": q, "s": s}

    return one(k_cache), one(v_cache)


def is_quantized_cache(leaf: Any) -> bool:
    return (
        isinstance(leaf, dict)
        and set(leaf) == {"q", "s"}
        and getattr(leaf["q"], "ndim", 0) == getattr(leaf["s"], "ndim", 0) + 1
    )


def dequant_cache(leaf, dtype=jnp.float32) -> jnp.ndarray:
    """Materialise a quantized cache back to ``dtype`` (the jnp fallback
    path; the Pallas kernel never materialises it)."""
    return (leaf["q"].astype(jnp.float32) * leaf["s"][..., None]).astype(dtype)


def quantize_kv_vector(vec: jnp.ndarray, levels=KV_INT8_LEVELS):
    """One new cache entry ``[..., D]`` → (int8 codes, f32 scales [...])
    — the decode-step write path. ``levels`` is the 127 the scale divides
    by. Inside a compiled program XLA turns a division by a literal into
    a multiplication by its reciprocal, one ulp away from what the same
    line gives when it runs eagerly (the solo path's bulk quantization of
    a prompt); a program that must write those very scales passes its 127
    as a runtime value (a session's row install does), and divides."""
    vf = vec.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(vf), axis=-1), 1e-8) / levels
    q = jnp.clip(jnp.round(vf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def params_nbytes(params: Dict[str, Any]) -> int:
    total = 0
    for leaf in params.values():
        if is_quantized(leaf):
            total += sum(v.nbytes for v in leaf.values())
        else:
            total += leaf.nbytes
    return total
