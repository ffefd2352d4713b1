"""Pallas TPU Kimi Delta Attention decode step: the live rows' state, once, where it lies.

A KDA layer's one-token step (``models/kda.py::_step``) is a pass over the
layer's recurrent state ``s [B, H, K, V]`` float32: every value is decayed
by its key channel's ``exp(g)``, the decayed state is read against ``k`` for
the delta ``u = beta (v - S^T k)``, gets ``k u^T`` added, and is read against
``q`` for the output. In plain XLA the pass runs over the WHOLE row bucket
of the record ``[Ls, B, H, K, V]`` (a row that is done has ``g = 0`` and
``beta = 0``: its state is read and written back as it was), where ~12 of
32 rows decode.

This is ``ops/pallas_ssm.py``'s kernel with KDA's arithmetic: the live rows
are the grid, the record arrives WHOLE and is aliased onto the result, the
layer's entry, the live rows' indices and their count are scalar-prefetched,
and a state tile's ``index_map`` picks ``(entry, row[j], head block)``. From
ONE tile in VMEM a grid step forms ``S' = Diag(exp g) S``, ``u``, ``S = S' + k
u^T`` and ``o = S^T q`` for its heads and writes the tile back where it came
from. The grid ends at the last LIVE row: a dead row's tiles are neither
fetched nor written, and its ``o`` is the zero the result started from.

The columns a head reads along its keys (``q``, ``k``, ``g``) ride with the
heads on LANES (``[B, H / hb, K, hb]``): a head's column ``[K, 1]``
broadcasts along the state's ``V`` lanes, and the reductions over ``K`` are
sublane sums that land in a row ``[1, V]`` -- the shape of ``v`` and of the
output, which ride with the heads on sublanes (``[B, H / hb, hb, V]``).

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
from .pallas_ssm import STEP_STATE_BYTES


def _head_block(heads: int, k: int, v: int) -> int:
    """Heads a grid step: the most that divide the heads with their state
    inside ``STEP_STATE_BYTES``; 0 where one head's does not fit."""
    return max(
        (
            hb for hb in range(1, heads + 1)
            if heads % hb == 0 and hb * k * v * 4 <= STEP_STATE_BYTES
        ),
        default=0,
    )


def kda_step_supported(s) -> bool:
    """Static gate: the record's ``s [Ls, B, H, K, V]`` in float32 with
    ``V`` in lane tiles and ``K`` in sublane tiles (a head's state is then
    whole vector registers), and a block of heads inside a grid step's
    tile."""
    if s.ndim != 5 or s.dtype != jnp.float32:
        return False
    h, k, v = s.shape[2:]
    return v % 128 == 0 and k % 8 == 0 and _head_block(h, k, v) > 0


def _kernel(
    at_ref, rows_ref, n_ref, s_ref, q_ref, k_ref, g_ref, v_ref, b_ref, o0_ref,
    s_out, o_ref, *, heads,
):
    """Grid (live row, head block): the block's state stepped in its tile
    and read against ``q``, a head at a time."""
    del at_ref, rows_ref, o0_ref  # read by the index maps; o0 IS o_ref's array
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _row():
        q, k, g = q_ref[0, 0], k_ref[0, 0], g_ref[0, 0]  # [K, hb]
        beta = b_ref[0, 0]  # [1, hb]
        decay = jnp.exp(g)
        for h in range(heads):
            col = slice(h, h + 1)
            kc = k[:, col]  # [K, 1]
            sd = decay[:, col] * s_ref[0, 0, h]  # [K, V]
            u = beta[:, col] * (
                v_ref[0, 0, col, :] - jnp.sum(sd * kc, axis=0, keepdims=True)
            )  # [1, V]
            s1 = sd + kc * u
            s_out[0, 0, h] = s1
            o_ref[0, 0, col, :] = jnp.sum(s1 * q[:, col], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _nobody():  # the one step of a grid with no live row
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def kda_step_live(
    s: jnp.ndarray,  # [Ls, B, H, K, V] float32: the record's state, whole
    at,  # int32 scalar: the layer's entry in it
    rows: jnp.ndarray,  # int32 [B]: the live rows first (pallas_ssm.live_rows)
    n_live,  # int32 scalar: how many of them
    q: jnp.ndarray,  # [B, H, K], normalised and scaled
    k: jnp.ndarray,  # [B, H, K], normalised
    v: jnp.ndarray,  # [B, H, V]
    g: jnp.ndarray,  # [B, H, K]: log-decay a key channel
    beta: jnp.ndarray,  # [B, H]
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(o [B, H, V], s)`` after one token of the rows ``rows[:n_live]``
    at entry ``at`` (float32, as ``models/kda.py::_step``). Every other
    row's and entry's state is what it was, and every other row's ``o`` is
    zero. ``s`` is written where it lies (donate it, or it is copied
    first)."""
    _, b, h, kd, vd = s.shape
    if not kda_step_supported(s):
        raise ValueError(
            "state outside the kernel's envelope (kda_step_supported); "
            "use the step of models/kda.py"
        )
    if interpret is None:
        interpret = not on_tpu()
    hb = _head_block(h, kd, vd)
    nhb = h // hb
    f32 = jnp.float32

    def cols(t):  # [B, H, K] -> [B, H / hb, K, hb]: the heads on lanes
        return jnp.swapaxes(t.astype(f32).reshape(b, nhb, hb, kd), 2, 3)

    prefetch = (
        jnp.reshape(at, (1,)).astype(jnp.int32),
        rows.astype(jnp.int32),
        jnp.reshape(n_live, (1,)).astype(jnp.int32),
    )

    def row_block(j, c, at, rows, n_live):
        return rows[j], c, 0, 0

    state_spec = pl.BlockSpec(
        (1, 1, hb, kd, vd), lambda j, c, at, rows, n_live: (at[0], rows[j], c, 0, 0)
    )
    col_spec = pl.BlockSpec((1, 1, kd, hb), row_block)
    head_rows = pl.BlockSpec((1, 1, hb, vd), row_block)  # v in, o out
    s_new, o = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        name="pallas_kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.clip(n_live, 1, b).astype(jnp.int32), nhb),
            in_specs=[
                state_spec, col_spec, col_spec, col_spec, head_rows,
                pl.BlockSpec((1, 1, 1, hb), row_block),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[state_spec, head_rows],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, f32),
            jax.ShapeDtypeStruct((b, nhb, hb, vd), f32),
        ],
        # operands count from the scalar-prefetched three: the state and
        # the zeros that o starts from are written where they lie
        input_output_aliases={3: 0, 9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        *prefetch, s, cols(q), cols(k), cols(g),
        v.astype(f32).reshape(b, nhb, hb, vd),
        beta.astype(f32).reshape(b, nhb, 1, hb),
        jnp.zeros((b, nhb, hb, vd), f32),
    )
    return o.reshape(b, h, vd), s_new
