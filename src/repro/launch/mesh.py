"""Mesh builders.

Functions, not module-level constants, so importing this module never
touches jax device state."""
from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods via the leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh():
    """(data, model) mesh over the local devices, as square as the device
    count allows: 1 chip -> 1x1, 4 chips -> 2x2, 8 -> 4x2."""
    n = jax.device_count()
    model = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
