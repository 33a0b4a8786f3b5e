"""Production meshes (system-prompt contract).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to fabricate the placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (data, model) single pod (256 chips, v5e-like) or
    2×16×16 (pod, data, model) for the two-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh():
    """(n, 1) (data, model) mesh over every device of this process."""
    return _make_mesh((jax.device_count(), 1), ("data", "model"))
