"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run pins
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
init, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) data×model = 256 chips; multi-pod adds a
    leading "pod" axis: (2, 16, 16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh helper for tests/examples (e.g. (1,1) on CPU)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), **_axis_kwargs(len(axes))
    )
