"""Rotary position embeddings (GPT-NeoX half-rotation layout).

All 7 reference model families use RoPE with per-family ``rope_theta``
(e.g. llama3.1 5e5, qwen2 1e6). Angles are computed in float32 and applied as
a half-split rotation: x = [x1, x2] → [x1·cos − x2·sin, x2·cos + x1·sin].
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp


def yarn_mscale(factor: float, a: float) -> float:
    """YaRN's magnitude correction ``m(s, a) = 0.1 a ln(s) + 1`` (1 for
    ``s <= 1``)."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_ramp_bounds(scaling: Any, d_head: int, theta: float) -> Tuple[int, int]:
    """``(low, high)``: the rotary dimensions between which YaRN ramps
    from kept to divided frequencies. ``corr(r)`` is the dimension whose
    wavelength makes ``r`` turns over the original context."""

    def corr(turns: float) -> float:
        return (
            d_head
            * math.log(scaling.original_max_position / (2 * math.pi * turns))
            / (2 * math.log(theta))
        )

    low = max(math.floor(corr(scaling.beta_fast)), 0)
    high = min(math.ceil(corr(scaling.beta_slow)), d_head - 1)
    return low, high


def rope_score_scale(scaling: Optional[Any]) -> float:
    """What YaRN multiplies an attention score by beside ``1 / sqrt(d)``:
    ``m(factor, mscale_all_dim)^2`` (1 without scaling)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def rope_angles(
    positions: jnp.ndarray,
    d_head: int,
    theta: float,
    scaling: Optional[Any] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions; shapes [..., d_head//2].

    ``scaling`` (``models/config.py`` ``RopeScaling``) is YaRN in
    DeepSeek-V3's form, applied at every position: dimension ``i`` keeps
    its frequency below ``low``, has it divided by ``factor`` above
    ``high``, a linear ramp between; cos and sin are scaled by
    ``m(factor, mscale) / m(factor, mscale_all_dim)``. Without it the
    tables are what they always were."""
    half = d_head // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if scaling is not None:
        low, high = yarn_ramp_bounds(scaling, d_head, theta)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / (high - low if high != low else 0.001),
            0.0,
            1.0,
        )
        freqs = freqs * (1.0 - ramp) + (freqs / scaling.factor) * ramp
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        magnitude = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
        if magnitude != 1.0:
            cos, sin = cos * magnitude, sin * magnitude
    return cos, sin


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate the head dimension. x: [..., n_heads, d_head]; cos/sin broadcast
    over the head axis as [..., 1, d_head//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1f = x1.astype(jnp.float32)
    x2f = x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)
