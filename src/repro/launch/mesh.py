"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import (see dryrun.py) to obtain enough placeholder devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests / smoke runs)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh(
        (n // model_axis, model_axis), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (the pod axis folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for a in data_axes(mesh):
        s *= mesh.shape[a]
    return s


def tp_size(mesh) -> int:
    return mesh.shape["model"]
