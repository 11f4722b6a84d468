"""Production meshes.

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
paper's proxy-region boundary — cheap wide links inside, expensive links
across (DCI), exactly the cost structure proxy regions exploit.

Functions, never module-level constants: importing this module must not
touch jax device state (the dry-run pins the device count *before* any
jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the shardings here are
    placement hints that the compiler propagates (GSPMD), not the
    ``Explicit`` sharding-in-types that ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
