"""Production mesh construction and virtual CPU devices (deliverable e).

Functions, not module-level constants — importing this module never
touches jax device state (required because the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init,
while tests/benches must see the single real CPU device).
"""
from __future__ import annotations

import os

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

