"""Percentiles and peaks: the arithmetic every metric shares.

``percentile`` is numpy's linear interpolation, as the program's
``serving/metrics.latency_percentiles`` computes it.  ``PEAKS`` holds the
published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float          # dense bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    source: str


#: keyed by ``device_kind`` as JAX reports it
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    """A device that is not in the table is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/harness/"
                         "stats.PEAKS with their source") from None
