"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. The yardstick's own table: a PR may change
the program's table, not this one. A kind that is missing is an error,
never a default."""

from __future__ import annotations

from typing import Dict

CHIP_PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip",
    },
}


class UnknownChip(RuntimeError):
    pass


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise UnknownChip(
            f"device kind {device_kind!r} is not in the benchmark's table of "
            f"peaks ({sorted(CHIP_PEAKS)}); add it with its source"
        ) from None
