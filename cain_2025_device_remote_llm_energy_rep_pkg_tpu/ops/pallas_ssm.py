"""Pallas TPU state-space decode step: the live rows' state, once, where it lies.

A state-space layer's one-token step (``models/ssm.py::_step``) is a pass
over the layer's recurrent state ``s [B, H, P, N]`` float32: every value
is decayed, gets its share of the token's outer product, and is contracted
with ``C`` for the layer's output. In plain XLA the pass runs over the
WHOLE row bucket of the record ``[Ls, B, H, P, N]`` (a row that is done
has ``dt = 0``: its state is read, multiplied by 1 and written back) and
the state is read a second time for ``S C``: in the granite cell 36
layers x 32 rows x 4.2 MB, twice and a half, where ~12 rows decode.

Here the live rows are the grid. The record arrives WHOLE as it lies and
is aliased onto the result; the layer's entry, the live rows' indices and
their count are scalar-prefetched, and a state tile's ``index_map`` picks
``(entry, row[j], head block)``. From ONE tile in VMEM a grid step forms
both ``S_t = a S_{t-1} + dt x (outer) B`` and ``y = S_t C + D x``, and
writes the tile back where it came from. The grid ends at the last LIVE
row (a dynamic bound): a dead row's tiles are neither fetched nor written,
so its state is bit-for-bit what it was, and its ``y`` is the zero the
result started from (``y`` too is written onto an operand). With no live
row the one grid step there must be copies a tile of row 0 onto itself.

The step's small operands ride with the heads on LANES (``x`` as ``[B,
H / hb, P, hb]``): a head's column ``[P, 1]`` then broadcasts along the
state's ``N`` lanes, the lane reduction of ``S C`` lands in such a
column, and no value changes between sublanes and lanes inside the
kernel. The wrapper turns ``_step``'s operands into that form and ``y``
back; both are a bucket's worth of ``[B, H, P]`` values, 1/128 of a
layer's state.

Arithmetic is ``_step``'s first form in float32 throughout: products
and sums on the VPU, only the order of the sum over ``N`` inside ``y``
differs.

On non-TPU backends the kernel runs in interpret mode so CPU tests
exercise the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import on_tpu

# State bytes one grid step reads (and writes): large against the fixed
# cost of a grid step, small against a row's state (the call's first read
# and last write are its fill and drain), and four buffers of it far
# inside VMEM. On the chip 0.5 to 4 MB read the same to 0.3%: the call is
# bound by its two DMA streams (scripts/ssm_step_bench.py).
STEP_STATE_BYTES = 1 << 20


def _head_block(heads: int, groups: int, p: int, n: int) -> int:
    """Heads a grid step: the most that divide a group's heads (a tile
    reads ONE group's ``B`` and ``C``) with their state inside
    ``STEP_STATE_BYTES``; 0 where one head's does not fit."""
    per_group = heads // groups
    return max(
        (
            hb for hb in range(1, per_group + 1)
            if per_group % hb == 0 and hb * p * n * 4 <= STEP_STATE_BYTES
        ),
        default=0,
    )


def ssm_step_supported(s, groups: int) -> bool:
    """Static gate: the record's ``s [Ls, B, H, P, N]`` in float32 with
    ``N`` in lane tiles and ``P`` in sublane tiles (a head's state is then
    whole vector registers), heads that divide into the groups, and one
    head's state inside a grid step's tile."""
    if s.ndim != 5 or s.dtype != jnp.float32 or groups < 1:
        return False
    h, p, n = s.shape[2:]
    return (
        n % 128 == 0
        and p % 8 == 0
        and h % groups == 0
        and _head_block(h, groups, p, n) > 0
    )


def live_rows(mask: Optional[jnp.ndarray], batch: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(rows int32 [B], n int32)``: the rows ``mask [B]`` marks, first and
    in order (the rest of the list names row 0 and is never visited), and
    their count; without a mask every row."""
    if mask is None:
        return jnp.arange(batch, dtype=jnp.int32), jnp.int32(batch)
    rows = jnp.nonzero(mask, size=batch, fill_value=0)[0]
    return rows.astype(jnp.int32), jnp.sum(mask, dtype=jnp.int32)


def _kernel(
    at_ref, rows_ref, n_ref, s_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
    y0_ref, o_ref, y_ref, *, heads,
):
    """Grid (live row, head block): the block's state updated in its tile
    and contracted with ``C``, a head at a time."""
    del at_ref, rows_ref, y0_ref  # read by the index maps; y0 IS y_ref's array
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _row():
        dt = dt_ref[0, 0]  # [1, hb]
        x = x_ref[0, 0]  # [P, hb]
        a = jnp.exp(dt * a_ref[0])
        dx = dt * x
        skip = d_ref[0] * x
        b, c = b_ref[0, 0], c_ref[0, 0]  # [1, N]
        for h in range(heads):
            col = slice(h, h + 1)
            s1 = a[:, col] * s_ref[0, 0, h] + dx[:, col] * b  # [P, N]
            o_ref[0, 0, h] = s1
            y_ref[0, 0, :, col] = (
                jnp.sum(s1 * c, axis=-1, keepdims=True) + skip[:, col]
            )

    @pl.when(jnp.logical_not(live))
    def _nobody():  # the one step of a grid with no live row
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_step_live(
    s: jnp.ndarray,  # [Ls, B, H, P, N] float32: the record's state, whole
    at,  # int32 scalar: the layer's entry in it
    rows: jnp.ndarray,  # int32 [B]: the live rows first (live_rows)
    n_live,  # int32 scalar: how many of them
    x: jnp.ndarray,  # [B, H, P]
    bm: jnp.ndarray,  # [B, G, N]
    cm: jnp.ndarray,  # [B, G, N]
    dt: jnp.ndarray,  # [B, H], softplus taken
    a_neg: jnp.ndarray,  # [H]
    d_skip: jnp.ndarray,  # [H]
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(y [B, H, P], s)`` after one token of the rows ``rows[:n_live]``
    at entry ``at``: ``S = exp(dt A) S + dt x (outer) B`` and ``y = S C +
    D x`` (float32, as ``models/ssm.py::_step``). Every other row's and
    entry's state is what it was, and every other row's ``y`` is zero.
    ``s`` is written where it lies (donate it, or it is copied first)."""
    _, b, h, p, n = s.shape
    g = bm.shape[1]
    if not ssm_step_supported(s, g):
        raise ValueError(
            "state outside the kernel's envelope (ssm_step_supported); "
            "use the step of models/ssm.py"
        )
    if interpret is None:
        interpret = not on_tpu()
    hb = _head_block(h, g, p, n)
    nhb, blocks_a_group = h // hb, h // g // hb
    f32 = jnp.float32

    def lanes(t):  # [..., H] -> [..., H / hb, 1, hb]: the heads on lanes
        return t.astype(f32).reshape(*t.shape[:-1], nhb, 1, hb)

    x_l = jnp.swapaxes(x.astype(f32).reshape(b, nhb, hb, p), 2, 3)  # [B, nhb, P, hb]
    prefetch = (
        jnp.reshape(at, (1,)).astype(jnp.int32),
        rows.astype(jnp.int32),
        jnp.reshape(n_live, (1,)).astype(jnp.int32),
    )

    def row_block(j, k, at, rows, n_live):
        return rows[j], k, 0, 0

    def head_block(j, k, *_):
        return k, 0, 0

    def group_block(j, k, at, rows, n_live):
        return rows[j], k // blocks_a_group, 0, 0

    state_spec = pl.BlockSpec(
        (1, 1, hb, p, n), lambda j, k, at, rows, n_live: (at[0], rows[j], k, 0, 0)
    )
    column_spec = pl.BlockSpec((1, 1, p, hb), row_block)  # x in, y out
    s_new, y_l = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        name="pallas_ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.clip(n_live, 1, b).astype(jnp.int32), nhb),
            in_specs=[
                state_spec,
                column_spec,
                pl.BlockSpec((1, 1, 1, hb), row_block),
                pl.BlockSpec((1, 1, hb), head_block),
                pl.BlockSpec((1, 1, hb), head_block),
                pl.BlockSpec((1, 1, 1, n), group_block),
                pl.BlockSpec((1, 1, 1, n), group_block),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[state_spec, column_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, f32),
            jax.ShapeDtypeStruct((b, nhb, p, hb), f32),
        ],
        # operands count from the scalar-prefetched three: the state and
        # the zeros that y starts from are written where they lie
        input_output_aliases={3: 0, 10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        *prefetch, s, x_l, lanes(dt), lanes(a_neg), lanes(d_skip),
        bm.astype(f32)[:, :, None], cm.astype(f32)[:, :, None],
        jnp.zeros((b, nhb, p, hb), f32),
    )
    return jnp.swapaxes(y_l, 2, 3).reshape(b, h, p), s_new
