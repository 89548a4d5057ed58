"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import (see dryrun.py); tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for in-process distributed tests (8 fake devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
