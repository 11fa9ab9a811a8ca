"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required by the dry-run protocol.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips (data, model).
    Multi-pod: (2, 16, 16) = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Elastic variant: any shape over the available devices (used by the
    fault-tolerance runtime to rebuild a smaller mesh after node loss)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
