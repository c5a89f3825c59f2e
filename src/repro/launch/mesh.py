"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — dryrun.py must
set XLA_FLAGS before anything here runs).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_smoke_mesh():
    """Whatever devices exist locally, as a 1D (data,) mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=_auto(("data",)))


def _auto(axes) -> tuple:
    """Auto axis types: ``jax.make_mesh`` defaults to Explicit axes,
    which ``with_sharding_constraint`` on named specs rejects."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def dp_axes(mesh) -> Union[str, Tuple[str, ...]]:
    """The data-parallel / FSDP axes: ('pod','data') when a pod axis
    exists, else 'data'."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else "data"


def dp_size(mesh) -> int:
    names = mesh.axis_names
    n = mesh.shape["data"]
    if "pod" in names:
        n *= mesh.shape["pod"]
    return n


def mdl_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
