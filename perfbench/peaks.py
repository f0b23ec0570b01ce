"""The one table of device peaks, keyed by ``device_kind``.  A device that
is not in the table is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect).  ``device_kind`` as jax 0.9.0 /
libtpu 0.0.34 report it on that chip is "TPU v5 lite" (PR 21, chip run).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; the table has "
            f"{sorted(PEAKS)}. Add the device with its source, do not "
            "default it.") from None
