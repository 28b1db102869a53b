"""Production mesh construction and the per-chip peak table (deliverable e).

Functions, not module-level constants — importing this module never
touches jax device state (required because the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init,
while tests/benches must see the single real CPU device).
"""
from __future__ import annotations

import os
from typing import Dict, NamedTuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding rules place arrays with NamedSharding and
    # leave propagation to the partitioner (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def request_cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` virtual devices, so a mesh can be
    rehearsed without accelerators — only when this process is held to
    the CPU (``JAX_PLATFORMS=cpu``) and XLA_FLAGS does not already fix
    the count.  A no-op on any other platform.  Must run before JAX
    initialises a backend."""
    if jax.config.jax_platforms != "cpu":
        return
    if "--xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return
    jax.config.update("jax_num_cpu_devices", n)


class ChipPeaks(NamedTuple):
    flops_bf16: float          # FLOP/s
    hbm_bw: float              # B/s
    ici_bw_per_link: float     # B/s per link
    source: str


# Keyed by ``jax.Device.device_kind``.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw_per_link=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links)"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks for ``device_kind``; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None

