"""What JAX is attached to: the one TPU predicate and the device report.

Every kernel's interpret switch, the engine's kernel selection and the
benches ask :func:`on_tpu`; ``serve`` logs :func:`device_report` at
start-up and ``/debug/state`` serves it, so a client can refuse a
server that is not on the chip it expects before any model loads.
"""

from __future__ import annotations

from typing import Any, Dict


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (Pallas kernels lower
    through Mosaic there and run in interpret mode anywhere else)."""
    import jax

    return jax.default_backend() == "tpu"


def device_report() -> Dict[str, Any]:
    """``{"platform", "kind", "count"}`` as JAX reports them
    (``devices()[0].platform``, ``.device_kind``, ``len(devices())``)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
