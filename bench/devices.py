"""Published facts of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
"""

from __future__ import annotations

CHIPS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def facts(device_kind: str) -> dict:
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(f"no published facts for device kind "
                       f"{device_kind!r}; add them to bench/devices.py"
                       ) from None
