"""Pallas TPU grouped expert FFN: one pipelined program over a layer's blocks.

An expert layer's token-expert pairs are grouped by expert into blocks of
``rows`` pairs (``models/transformer.py::_moe_parts``). A block reads ITS
expert's three matrices once and nothing else's, so the layer is a weight
stream over the experts that were chosen: 33 blocks against 11-MB experts
a decode step of the xing4 cell, ~2 against 37.75 MB in longcat's. As a
device ``while`` of one gated FFN a trip, nothing of block j+1 starts
before block j has ended, and every trip pays the fill and drain of each
of its matmuls' weight streams, a row gather and a scatter-add.

Here the blocks are the grid. The three expert leaves arrive WHOLE as they
lie (``[L, E, in, out]``, int8 codes with per-output-channel scales, or a
plain bfloat16 array); the layer's index, each block's expert and the
count of real blocks are scalar-prefetched, and a weight tile's
``index_map`` picks ``(layer, block_expert[j], tile)``: the tiles of
block j+1, another expert's, are in flight while block j is in the MXU.
Two calls: gate and up together over tiles of the expert width, then down
over tiles of its output; the activation crosses HBM between them in the
activations' dtype, a few KB a block.

The grid ends at the last REAL block (a dynamic bound): the static bound
on blocks (``tokens x top_k`` pairs can make ``min(E, pairs) + pairs //
rows`` of them) is 2.4 times the real ones in a decode step of the xing4
cell and 25 times in longcat's join chunk, and a step that only skips
still costs its turn of the pipeline. For the same reason the blocks' rows
never exist outside the kernels: the tokens stay whole in VMEM, the first
call gathers a block's rows by the scalar-prefetched token of each slot,
and the second adds each weighted result row to its token's row of the
output, which stays in VMEM across the blocks of one output tile. What the
layer pays besides its weights is then proportional to its real blocks.

Arithmetic is ``_expert_ffn``'s: int8 codes converted to the activations'
dtype inside the kernel, products accumulated in float32, the scales
multiplied onto the float32 result; results are added to their tokens in
block order, as the loop adds them. The contraction runs in chunks of at
most 512, so only the order of the float32 sums inside a matmul differs.

On non-TPU backends the kernel runs in interpret mode so CPU tests
exercise the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu

# Weight bytes one grid step streams (both tiles of the gate+up call, the
# one of the down call). Large against the fixed cost of a grid step,
# small enough that two buffers of it and the converted chunk stay far
# inside VMEM; the fill and the drain of a call are one such step each.
STEP_WEIGHT_BYTES = 4 << 20
# Rows of the contraction converted and multiplied at a time.
CONTRACT_CHUNK = 512
# The tokens (float32, whole) and an output tile of all tokens stay in
# VMEM, two buffers each: a 256-token chunk at a width of 6144 is 6.3 MB.
MAX_TOKEN_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 64 << 20


def _weights(leaf):
    """(matrix ``[L, E, in, out]``, scales ``[L, E, 1, out]`` or None)."""
    if isinstance(leaf, dict):
        return leaf["q"], leaf["s"]
    return leaf, None


def _leaf_fits(leaf) -> bool:
    if isinstance(leaf, dict):
        return set(leaf) == {"q", "s"} and leaf["q"].dtype == jnp.int8
    return leaf.dtype == jnp.bfloat16


def grouped_ffn_supported(x_dtype, tokens: int, rows: int, gate, up, down) -> bool:
    """Static gate: int8 ``{"q", "s"}`` or plain bfloat16 leaves ``[L, E,
    D, F]`` / ``[L, E, F, D]`` with ``D`` and ``F`` multiples of 128
    (lane tiles; a chunk of the contraction is then a whole number of
    int8 sublane tiles), blocks of whole sublane tiles of rows, bfloat16
    or float32 activations, and no more tokens than stay in VMEM whole."""
    if not all(_leaf_fits(leaf) for leaf in (gate, up, down)):
        return False
    d, f = _weights(gate)[0].shape[-2:]
    return (
        jnp.dtype(x_dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        and rows >= 8
        and rows % 8 == 0
        and d % 128 == 0
        and f % 128 == 0
        and tokens * d * 4 <= MAX_TOKEN_BYTES
    )


def _tile(n_in: int, n_out: int, leaves: int, itemsize: int) -> int:
    """Columns of a weight tile ``[n_in, tile]``: the widest multiple of
    128 that divides ``n_out`` with ``leaves`` such tiles inside
    ``STEP_WEIGHT_BYTES`` (at least 128)."""
    best = 128
    for tile in range(128, n_out + 1, 128):
        if n_out % tile == 0 and leaves * n_in * tile * itemsize <= STEP_WEIGHT_BYTES:
            best = tile
    return best


def _dot(x, w_ref, s_ref):
    """``x [rows, in] @ tile [in, cols]`` in float32, the scales applied."""
    n_in = w_ref.shape[-2]
    chunk = next(c for c in (CONTRACT_CHUNK, 256, 128) if n_in % c == 0)
    acc = None
    for k0 in range(0, n_in, chunk):
        part = jnp.dot(
            x[:, k0 : k0 + chunk],
            w_ref[0, 0, k0 : k0 + chunk, :].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc if s_ref is None else acc * s_ref[0, 0]


def _gate_up_kernel(
    li_ref, be_ref, nb_ref, tok_ref, h_ref, *refs, scaled, activation, rows, dtype,
):
    """Grid (block, tile of F): ``act(x G) * (x U)`` for the block's rows,
    gathered from the tokens at the block's first tile."""
    del li_ref, be_ref  # read by the index maps
    x_ref, o_ref = refs[-1], refs[-2]
    operands = iter(refs[:-2])
    gate, up = [(next(operands), next(operands) if s else None) for s in scaled]
    # (program ids are read at the top: interpret mode resolves them there)
    j, tile = pl.program_id(0), pl.program_id(1)

    @pl.when(j < nb_ref[0])
    def _block():
        @pl.when(tile == 0)
        def _gather():
            def row(r, _):
                x_ref[pl.ds(r, 1), :] = h_ref[pl.ds(tok_ref[j * rows + r], 1), :]
                return _

            jax.lax.fori_loop(0, rows, row, 0)

        x = x_ref[...].astype(dtype)
        y = _dot(x, *gate)
        if activation == "gelu":
            y = jax.nn.gelu(y, approximate=True)
        else:
            y = jax.nn.silu(y)
        o_ref[0] = (y * _dot(x, *up)).astype(o_ref.dtype)


def _down_kernel(
    li_ref, be_ref, nb_ref, tok_ref, a_ref, w_ref, *refs, scaled, rows,
):
    """Grid (tile of D, block): ``(a D) * w`` added to the rows' tokens in
    the output tile, which stays in VMEM across the tile's blocks."""
    del li_ref, be_ref
    y_ref, o_ref = refs[-1], refs[-2]
    down = (refs[0], refs[1] if scaled[0] else None)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < nb_ref[0])
    def _block():
        y_ref[...] = _dot(a_ref[0], *down) * w_ref[0]

        def row(r, _):
            at = pl.ds(tok_ref[j * rows + r], 1)
            o_ref[at, :] = o_ref[at, :] + y_ref[pl.ds(r, 1), :]
            return _

        jax.lax.fori_loop(0, rows, row, 0)


def grouped_expert_ffn(
    h: jnp.ndarray,  # [T, D] the tokens
    gate,  # [L, E, D, F] leaf (int8 {"q", "s"} or bfloat16)
    up,  # [L, E, D, F]
    down,  # [L, E, F, D]
    li,  # int32 scalar: the layer
    block_expert: jnp.ndarray,  # int32 [NB]: each block's expert, in [0, E)
    n_blocks,  # int32 scalar: the real blocks, the first of the NB
    slot_token: jnp.ndarray,  # int32 [NB * rows]: the token of each block row
    slot_weight: jnp.ndarray,  # float32 [NB * rows]: its weight, 0 on padding
    *,
    activation: str = "silu",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``sum over the real blocks' rows of w * (act(x G) * (x U)) D`` by
    token, float32 ``[T, D]``: row ``r`` of block ``j`` is token
    ``slot_token[j * rows + r]`` on expert ``block_expert[j]`` of layer
    ``li``. Blocks from ``n_blocks`` on are neither read nor computed;
    with none the result is zero."""
    t, d = h.shape
    nb_max = block_expert.shape[0]
    rows = slot_token.shape[0] // nb_max
    if not grouped_ffn_supported(h.dtype, t, rows, gate, up, down):
        raise ValueError(
            "leaves or shapes outside the kernel's envelope "
            "(grouped_ffn_supported); use the loop of _moe_parts"
        )
    if interpret is None:
        interpret = not on_tpu()
    mats = [_weights(leaf) for leaf in (gate, up, down)]
    scaled = tuple(s is not None for _, s in mats)
    f = mats[0][0].shape[-1]
    itemsize = mats[0][0].dtype.itemsize
    tile_f = _tile(d, f, 2, itemsize)
    tile_d = _tile(f, d, 1, itemsize)
    grid_blocks = jnp.clip(n_blocks, 1, nb_max).astype(jnp.int32)
    prefetch = (
        jnp.reshape(li, (1,)).astype(jnp.int32),
        block_expert.astype(jnp.int32),
        jnp.reshape(n_blocks, (1,)).astype(jnp.int32),
        slot_token.astype(jnp.int32),
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )

    def weight_specs(mats, n_in, tile, index):
        specs, operands = [], []
        for q, s in mats:
            specs.append(pl.BlockSpec((1, 1, n_in, tile), index))
            operands.append(q)
            if s is not None:
                specs.append(pl.BlockSpec((1, 1, 1, tile), index))
                operands.append(s.astype(jnp.float32))
        return specs, operands

    specs, operands = weight_specs(
        mats[:2], d, tile_f, lambda j, n, li, be, nb, tok: (li[0], be[j], 0, n)
    )
    act = pl.pallas_call(
        functools.partial(
            _gate_up_kernel, scaled=scaled[:2], activation=activation,
            rows=rows, dtype=h.dtype,
        ),
        name="pallas_moe_gate_up",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(grid_blocks, f // tile_f),
            in_specs=[pl.BlockSpec((t, d), lambda j, n, *_: (0, 0))] + specs,
            out_specs=pl.BlockSpec((1, rows, tile_f), lambda j, n, *_: (j, 0, n)),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nb_max, rows, f), h.dtype),
        compiler_params=params,
        interpret=interpret,
    )(*prefetch, h.astype(jnp.float32), *operands)

    specs, operands = weight_specs(
        mats[2:], f, tile_d, lambda n, j, li, be, nb, tok: (li[0], be[j], 0, n)
    )
    return pl.pallas_call(
        functools.partial(_down_kernel, scaled=scaled[2:], rows=rows),
        name="pallas_moe_down",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(d // tile_d, grid_blocks),
            in_specs=[
                pl.BlockSpec((1, rows, f), lambda n, j, *_: (j, 0, 0)),
                pl.BlockSpec((1, rows, 1), lambda n, j, *_: (j, 0, 0)),
            ] + specs,
            out_specs=pl.BlockSpec((t, tile_d), lambda n, j, *_: (0, n)),
            scratch_shapes=[pltpu.VMEM((rows, tile_d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(
        *prefetch, act,
        slot_weight.astype(jnp.float32).reshape(nb_max, rows, 1), *operands,
    )
