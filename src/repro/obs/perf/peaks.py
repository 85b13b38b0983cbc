"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every roofline figure in the repo divides by.  A device
that is not listed has no peaks: callers write no roofline or rate field
for it rather than borrow another chip's numbers.

Sources
-------
``TPU v5 lite`` (TPU v5e) — Google Cloud documentation, "TPU v5e": 197
TFLOP/s bf16 per chip, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect over four links (50 GB/s per link).  No float32 peak is
published; the bf16 figure bounds float32 work from above, so a roofline
share computed against it is a lower bound.
"""
from __future__ import annotations

from typing import NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float            # FLOP/s per chip (bf16)
    hbm_bytes_per_s: float  # HBM bandwidth per chip
    ici_bytes_per_s: float  # inter-chip bandwidth per link
    source: str


V5E = "TPU v5 lite"

PEAKS = {
    V5E: Peaks(flops=197e12, hbm_bytes_per_s=819e9, ici_bytes_per_s=50e9,
               source="Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: Optional[str]) -> Optional[Peaks]:
    """The table's entry for ``device_kind``; None when it has none."""
    return PEAKS.get(device_kind) if device_kind else None
