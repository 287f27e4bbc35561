"""Production mesh builders.

All builders are FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — smoke tests keep seeing 1 CPU
device; only the dry-run process forces 512 host devices.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A small mesh over whatever devices exist (tests / examples)."""
    return _mesh((data, model), ("data", "model"))
