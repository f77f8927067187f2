"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state - the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes (its default is Explicit): the
    trainer places arrays with NamedShardings and lets XLA propagate."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); multi_pod stacks 2 pods -> 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return auto_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host devices for tests (e.g. 2x4 with device_count=8)."""
    return auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes the global batch shards over: ('pod','data') on multi-pod."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
