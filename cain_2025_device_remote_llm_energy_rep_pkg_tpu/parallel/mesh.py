"""Device-mesh construction.

Axis conventions used across the framework:
  ``dp`` — data parallel (batch dim)
  ``tp`` — tensor parallel (attention heads / FFN hidden; rides ICI)
  ``sp`` — sequence parallel (ring attention's token-shard axis)

A ``MeshSpec`` names the axes with sizes; ``build_mesh`` materialises it over
the visible devices (real TPU slice or virtual CPU devices).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes, in mesh-major order. -1 on exactly one axis means
    "all remaining devices"."""

    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def tp_only(cls, tp: int = -1) -> "MeshSpec":
        return cls(axes=(("tp", tp),))

    @classmethod
    def dp_tp(cls, dp: int, tp: int) -> "MeshSpec":
        return cls(axes=(("dp", dp), ("tp", tp)))

    @classmethod
    def dp_tp_sp(cls, dp: int, tp: int, sp: int) -> "MeshSpec":
        return cls(axes=(("dp", dp), ("tp", tp), ("sp", sp)))

    @classmethod
    def tp_ep(cls, tp: int, ep: int) -> "MeshSpec":
        """Tensor × expert parallelism (MoE serving)."""
        return cls(axes=(("tp", tp), ("ep", ep)))

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        wild = [name for name, size in sizes.items() if size == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed: {self.axes}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {dict(self.axes)} needs {fixed} devices, have {n_devices}"
            )
        return sizes


def build_mesh(
    spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Materialise ``spec`` over ``devices`` (default: the visible ones).
    A fully-sized spec smaller than the host — ``--tp 2`` on four chips
    — takes the first ``prod(sizes)`` devices; only a ``-1`` axis claims
    everything visible."""
    devices = list(devices) if devices is not None else jax.devices()
    if all(size > 0 for _, size in spec.axes):
        devices = devices[: math.prod(size for _, size in spec.axes)]
    sizes = spec.resolve(len(devices))
    names = tuple(sizes.keys())
    shape = tuple(sizes.values())
    import numpy as np

    return Mesh(np.asarray(devices).reshape(shape), names)
