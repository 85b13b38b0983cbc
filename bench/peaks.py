"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of the program's table (``repro.obs.perf.peaks``), kept with the
benchmark so that a change to the program cannot move what a roofline
share is measured against.  A device that is not listed has no peaks:
a reader reports no share for it rather than borrow another chip's.

``TPU v5 lite`` (TPU v5e) — Google Cloud documentation, "TPU v5e": 197
TFLOP/s bf16 per chip, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect over four links (50 GB/s per link).  No float32 peak is
published; the bf16 figure bounds float32 work from above.
"""
from __future__ import annotations

from typing import NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float            # FLOP/s per chip (bf16)
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_bytes_per_s: float  # inter-chip bandwidth per link
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
                         ici_bytes_per_s=50e9,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: Optional[str]) -> Optional[Peaks]:
    """The table's entry for ``device_kind``; None when it has none."""
    return PEAKS.get(device_kind) if device_kind else None
