"""Published per-chip peaks, keyed by the `device_kind` jax reports.

The ONE table: chip_smoke.py, the in-program MFU gauge
(observability/telemetry.py) and the auto-tuner's cost model all read
it. A device that is not in it is an error, never a default — scoring
an unknown chip against some other chip's peak is how rounds 1-2
understated MFU 2.3x.
"""
from __future__ import annotations

from typing import NamedTuple

__all__ = ["ChipPeaks", "PEAKS", "peaks_for_kind", "detect_peaks"]


class ChipPeaks(NamedTuple):
    bf16_flops: float        # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    source: str


_GCP = 'Google Cloud documentation, "{}"'

PEAKS = {
    "TPU v4": ChipPeaks(275e12, 1200e9, _GCP.format("TPU v4")),
    "TPU v5": ChipPeaks(459e12, 2765e9, _GCP.format("TPU v5p")),
    "TPU v5 lite": ChipPeaks(197e12, 819e9, _GCP.format("TPU v5e")),
    "TPU v6 lite": ChipPeaks(918e12, 1640e9, _GCP.format("TPU v6e")),
}


def peaks_for_kind(kind: str) -> ChipPeaks:
    """Exact-key lookup; an unknown `device_kind` raises."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"paddle_tpu/device/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None


def detect_peaks() -> ChipPeaks | None:
    """Peaks of device 0; None off-TPU (a utilization against a CPU
    'peak' would be noise). An unknown TPU kind raises."""
    from paddle_tpu.core.jax_compat import on_tpu
    if not on_tpu():
        return None
    import jax
    return peaks_for_kind(jax.devices()[0].device_kind)
