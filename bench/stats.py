"""Shared arithmetic of the benchmark's metrics."""
from __future__ import annotations

from typing import Dict, Optional


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: Dict) -> Optional[float]:
    """A kernel's share of the chip's roofline, in %: the least time the
    chip could take for ``flops`` operations and ``bytes_`` of HBM traffic
    (the larger of the two over the device's ``bf16_flops`` and
    ``hbm_bytes_per_s`` peaks, ``Run.peaks``) over the ``seconds`` it took.
    None where there is no time or no work to share."""
    if seconds <= 0 or not peaks:
        return None
    least = max(flops / peaks["bf16_flops"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
