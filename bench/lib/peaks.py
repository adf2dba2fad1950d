"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 per chip, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
f32 matrix multiplications at JAX's default precision take one bf16 pass
on this chip, so the bf16 rate is the peak of the cells' default-precision
f32 dots.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py")
    return PEAKS[device_kind]
