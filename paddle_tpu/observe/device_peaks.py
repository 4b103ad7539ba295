"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

THE one table every utilization number divides by.  A device that is not
here has no peak: MFU is then ``None`` in summaries and an error where it
was asked for by name - never another chip's number.  Add a row, with its
source, when the program meets a new part.
"""
from __future__ import annotations

from typing import Optional

from ..framework import flags as _flags

DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # int8), 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0,
                    "hbm_gb": 16.0,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def device_peak(device=None) -> Optional[dict]:
    """The table row of ``device`` (default: jax's first device), or
    None for a part the table does not know (the CPU included)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return DEVICE_PEAKS.get(device.device_kind)


def peak_tflops(override: Optional[float] = None) -> Optional[float]:
    """The bf16 peak an MFU divides by: ``override`` when given, else
    ``FLAGS_device_peak_tflops`` when set (> 0), else the table row of
    the live device; None where there is no such row."""
    if override is not None:
        return float(override) if override > 0.0 else None
    flag = float(_flags.flag("device_peak_tflops") or 0.0)
    if flag > 0.0:
        return flag
    row = device_peak()
    return row["bf16_tflops"] if row else None
