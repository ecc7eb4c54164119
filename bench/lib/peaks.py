"""Published peaks of one chip, keyed by ``device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
1,600 Gbit/s inter-chip interconnect per chip.  A kind that is not in
the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

CHIP_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(CHIP_PEAKS)})"
                         ) from None
