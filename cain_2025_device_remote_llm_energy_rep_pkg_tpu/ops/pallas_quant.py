"""Pallas TPU int4 dequant-matmul: unpack nibbles in VMEM, not in HBM.

Decode is HBM-bandwidth-bound: at bf16 every generated token streams the
full weight bytes once. int8 halves that; int4 halves it again — but only
if the packed bytes cross HBM→VMEM *packed*. XLA cannot fuse the
shift/concat unpack into a matmul operand read (it materialises the
dequantized weights per step, measured ~5× slower than bf16), so this
kernel does the unpack after the DMA: each grid step reads one
[block_k, block_n] int8 tile (two weights per byte), splits it into the
low/high nibbles, and issues two MXU dots against the matching halves of
``x``.

Packing layout (quantize.py ``quantize_tensor_int4``): the input-feature
axis is split in half — row i of the packed tile carries weight row i in
its low nibbles and row i + IN/2 in its high nibbles. Halves (not
even/odd interleave) so the unpack needs no cross-lane shuffle: the two
nibble planes are themselves contiguous weight tiles, each dotted with a
contiguous slice of ``x``.

Activations stay bf16/f32 and accumulate in f32 on the MXU; the
per-output-channel scale applies once at the final k-block (scales
commute with the k-sum). ``x`` rows pad to 8 (f32 sublane tile) — the
intended callers are decode-shaped matvecs (M ≤ 8: single-token decode,
small decode batches, the speculative verify window).

On non-TPU backends the kernel runs in interpret mode so CPU tests
exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu

# Row-count ceiling for the kernel path: one f32 sublane tile. Larger M
# (prefill) amortises the XLA dequant path fine.
MAX_KERNEL_ROWS = 8


def _pick_block(n: int, preferred: int) -> int:
    block = 1
    while n % (block * 2) == 0 and block * 2 <= preferred:
        block *= 2
    return block


def _int4_matmul_kernel(
    x_ref,  # VMEM [8, 2*in_half_pad] activations (halves at 0 and in_half_pad)
    p_ref,  # VMEM [block_k, block_n] int8 — packed nibble pairs
    s_ref,  # VMEM [1, block_n] f32 per-output-channel scales
    o_ref,  # VMEM [8, block_n]
    acc_ref,  # VMEM scratch [8, block_n] f32
    *,
    block_k: int,
    in_half: int,
    in_half_pad: int,
    n_k_blocks: int,
    masked_tail: bool,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = p_ref[...].astype(jnp.int32)
    if masked_tail:
        # Only reachable when no block_k divides in_half (rare awkward
        # dims): the tail block extends past the packed rows and its
        # out-of-bounds content is unspecified. The divisible fast path
        # skips these three VPU ops per element entirely.
        rows_valid = in_half - k * block_k
        row = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        p = jnp.where(row < rows_valid, p, 0)
    # Sign-extend the two 4-bit planes (arithmetic shifts) and dot in
    # bfloat16: the MXU runs bf16×bf16→f32 at full rate where an f32 dot
    # takes multiple passes, and 4-bit weights are exact in bf16 (|w|≤7),
    # so this loses no precision over the f32-operand version while
    # cutting both the convert cost and the MXU time. This unpack is the
    # kernel's VPU budget — keep it at 3 shifts + 2 converts per byte.
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28).astype(jnp.bfloat16)
    hi = jnp.right_shift(p, 4).astype(jnp.bfloat16)
    xl = x_ref[:, pl.ds(k * block_k, block_k)].astype(jnp.bfloat16)
    xh = x_ref[:, pl.ds(in_half_pad + k * block_k, block_k)].astype(jnp.bfloat16)
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        xl, lo, dims, preferred_element_type=jnp.float32
    ) + jax.lax.dot_general(xh, hi, dims, preferred_element_type=jnp.float32)

    @pl.when(k == n_k_blocks - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def int4_matmul_supported(m: int, in_half: int, out_dim: int) -> bool:
    """Static shape gate: int8 tiles need a 32-sublane, 128-lane block."""
    return (
        m <= MAX_KERNEL_ROWS
        and in_half % 32 == 0
        and out_dim % 128 == 0
    )


def _int4_matmul_kernel_i32(
    x_ref,  # VMEM [8, 8*k8_pad] activations, plane-major (see int4_matmul_i32)
    p_ref,  # VMEM [block_k8, block_n] int32 — 8 nibbles per lane
    s_ref,  # VMEM [1, block_n] f32
    o_ref,  # VMEM [8, block_n]
    acc_ref,  # VMEM scratch [8, block_n] f32
    *,
    block_k8: int,
    k8_pad: int,
    n_k_blocks: int,
):
    """The VERDICT-suggested alternative unpack: weights arrive as native
    i32 vectors (8 k-consecutive nibbles per lane), so extraction is pure
    i32 shift arithmetic — shl + arithmetic-shr sign-extends each plane,
    with no i8→i32 convert and no 4-per-lane relayout. Eight small MXU
    dots (one per nibble plane) replace the halves layout's two; the
    activation planes are pre-sliced host-side so each dot's operand is a
    contiguous VMEM slice."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p32 = p_ref[...]
    dims = (((1,), (0,)), ((), ()))
    acc = acc_ref[...]
    for plane in range(8):
        w = jnp.right_shift(
            jnp.left_shift(p32, 28 - 4 * plane), 28
        ).astype(jnp.bfloat16)
        xp = x_ref[
            :, pl.ds(plane * k8_pad + k * block_k8, block_k8)
        ].astype(jnp.bfloat16)
        acc += jax.lax.dot_general(
            xp, w, dims, preferred_element_type=jnp.float32
        )
    acc_ref[...] = acc

    @pl.when(k == n_k_blocks - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def int4_matmul_i32(
    x: jnp.ndarray,  # [M, IN], M <= 8
    packed32: jnp.ndarray,  # [IN/8, OUT] int32 (8 nibbles per lane)
    scale: jnp.ndarray,  # [1, OUT] f32
) -> jnp.ndarray:
    """``x @ dequant(packed32, scale)`` with the i32-lane nibble layout
    (quantize.quantize_tensor_int4_i32)."""
    m, in_dim = x.shape
    k8, out_dim = packed32.shape
    if in_dim != 8 * k8:
        raise ValueError(f"x in-dim {in_dim} != 8 * packed rows {k8}")
    if m > MAX_KERNEL_ROWS or out_dim % 128:
        raise ValueError(
            f"shape (m={m}, k8={k8}, out={out_dim}) outside the kernel "
            "envelope (out must be a multiple of 128)"
        )
    # Mosaic needs 128-lane-aligned slice offsets on the x planes and
    # sublane-tileable k blocks: pad k8 up to a 128 multiple with zero
    # lanes (zero nibbles decode to zero weights — they add nothing to the
    # dots, but their bytes DO stream; the padding overhead is part of
    # this layout's honest cost on non-aligned dims like 1536/8 = 192).
    k8_pad = -(-k8 // 128) * 128
    if k8_pad != k8:
        packed32 = jnp.pad(packed32, ((0, k8_pad - k8), (0, 0)))
    block_k8 = next(
        cand
        for cand in range(128 * (min(512, k8_pad) // 128), 127, -128)
        if k8_pad % cand == 0
    )
    block_n = 512 if out_dim >= 512 else _pick_block(out_dim, 512)
    n_k_blocks = k8_pad // block_k8
    grid = (-(-out_dim // block_n), n_k_blocks)

    # Plane-major activation repack: plane p (weight rows 8k+p) lives at
    # [p*k8_pad, p*k8_pad + k8). Cheap — x is [M, IN], thousands of
    # elements vs the megabytes of weight bytes each step streams.
    x_planes = x.reshape(m, k8, 8).transpose(0, 2, 1)  # [m, 8, k8]
    x8 = jnp.zeros((MAX_KERNEL_ROWS, 8, k8_pad), x.dtype)
    x8 = x8.at[:m, :, :k8].set(x_planes)
    x8 = x8.reshape(MAX_KERNEL_ROWS, 8 * k8_pad)

    kernel = functools.partial(
        _int4_matmul_kernel_i32,
        block_k8=block_k8,
        k8_pad=k8_pad,
        n_k_blocks=n_k_blocks,
    )
    out = pl.pallas_call(
        kernel,
        name="int4_matmul_i32",
        grid=grid,
        in_specs=[
            pl.BlockSpec((MAX_KERNEL_ROWS, 8 * k8_pad), lambda o, k: (0, 0)),
            pl.BlockSpec((block_k8, block_n), lambda o, k: (k, o)),
            pl.BlockSpec((1, block_n), lambda o, k: (0, o)),
        ],
        out_specs=pl.BlockSpec((MAX_KERNEL_ROWS, block_n), lambda o, k: (0, o)),
        out_shape=jax.ShapeDtypeStruct((MAX_KERNEL_ROWS, out_dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((MAX_KERNEL_ROWS, block_n), jnp.float32)],
        interpret=not on_tpu(),
    )(x8, packed32, scale.astype(jnp.float32))
    return out[:m]


def int4_matmul(
    x: jnp.ndarray,  # [M, IN], M <= 8
    packed: jnp.ndarray,  # [IN/2, OUT] int8 (halves-packed)
    scale: jnp.ndarray,  # [1, OUT] f32
) -> jnp.ndarray:
    """``x @ dequant(packed, scale)`` with the nibbles unpacked in VMEM."""
    m, in_dim = x.shape
    in_half, out_dim = packed.shape
    if in_dim != 2 * in_half:
        raise ValueError(f"x in-dim {in_dim} != 2 * packed rows {in_half}")
    if not int4_matmul_supported(m, in_half, out_dim):
        raise ValueError(
            f"shape (m={m}, in_half={in_half}, out={out_dim}) outside the "
            "kernel envelope; use the XLA dequant path"
        )
    # Prefer a block_k that DIVIDES in_half: the kernel then skips tail
    # masking, three fewer VPU ops per packed element on every block. Fall
    # back to a masked tail only for dims with no such divisor. The n-tail's
    # out-of-bounds output region is discarded by Pallas either way, so
    # block_n stays large for awkward dims (d_ff 8960 = 2^8·35 would
    # otherwise force 256-wide blocks and ~630 grid steps).
    # block_k must keep the x-slice offsets lane-aligned (Mosaic: dim-1
    # vector loads start at multiples of 128), so candidates are multiples
    # of 128; up to 1024 keeps the p tile ≤ 512 KB of VMEM.
    block_k = 0
    for cand in range(128 * (min(1024, in_half) // 128), 127, -128):
        if in_half % cand == 0:
            block_k = cand
            break
    masked_tail = block_k == 0
    if masked_tail:
        block_k = min(256, _pick_block(in_half, 256) if in_half < 256 else 256)
    block_n = 512 if out_dim >= 512 else _pick_block(out_dim, 512)
    n_k_blocks = -(-in_half // block_k)
    in_half_pad = n_k_blocks * block_k
    grid = (-(-out_dim // block_n), n_k_blocks)

    # Pack x's two halves at [0, in_half) and [in_half_pad, ·), zero-padded
    # so the kernel's aligned slices never clamp; pad rows to the f32 tile.
    x8 = jnp.zeros((MAX_KERNEL_ROWS, 2 * in_half_pad), x.dtype)
    x8 = x8.at[:m, :in_half].set(x[:, :in_half])
    x8 = x8.at[:m, in_half_pad : in_half_pad + in_half].set(x[:, in_half:])

    kernel = functools.partial(
        _int4_matmul_kernel,
        block_k=block_k,
        in_half=in_half,
        in_half_pad=in_half_pad,
        n_k_blocks=n_k_blocks,
        masked_tail=masked_tail,
    )
    out = pl.pallas_call(
        kernel,
        name="int4_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (MAX_KERNEL_ROWS, 2 * in_half_pad), lambda o, k: (0, 0)
            ),  # whole x resident
            pl.BlockSpec((block_k, block_n), lambda o, k: (k, o)),
            pl.BlockSpec((1, block_n), lambda o, k: (0, o)),
        ],
        out_specs=pl.BlockSpec((MAX_KERNEL_ROWS, block_n), lambda o, k: (0, o)),
        out_shape=jax.ShapeDtypeStruct((MAX_KERNEL_ROWS, out_dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((MAX_KERNEL_ROWS, block_n), jnp.float32)],
        interpret=not on_tpu(),
    )(x8, packed, scale.astype(jnp.float32))
    return out[:m]
