"""Published peaks of each accelerator, keyed by ``device.device_kind``.

A device that is not in the table is an error, not a default: a roofline
share against the wrong chip's peak means nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16 and 819 GB/s
    # of HBM bandwidth per chip. f32 matmuls at HIGHEST precision take several
    # bf16 passes, so a compute-bound f32 kernel stays well below this peak.
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """{"flops_per_s", "hbm_bytes_per_s"} of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
