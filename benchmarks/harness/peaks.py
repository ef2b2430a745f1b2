"""Published peaks of the chips the benchmark may run on, keyed by jax's
``device_kind``. A device that is not in the table is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"name": "TPU v5e", "bf16_flops": 197e12,
                    "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; the table "
            f"in benchmarks/harness/peaks.py holds {sorted(PEAKS)}") from None
