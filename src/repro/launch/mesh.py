"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, everything else sees the real single CPU device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod (256 chips); (pod=2, data=16,
    model=16) for the 2-pod 512-chip deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def fsdp_axes(multi_pod: bool = False):
    """Axes over which parameters/optimizer state are fully sharded."""
    return ("pod", "data") if multi_pod else ("data",)


def make_smoke_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
