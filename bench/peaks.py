"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect.

The models this benchmark runs are float32, but on a TPU a float32 matrix
multiplication at JAX's default precision runs as bfloat16 passes, so the
bfloat16 rate is the ceiling every utilisation here is measured against.
A device kind missing from the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

SOURCE = "Google Cloud documentation, 'TPU v5e'"


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
