"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run launcher sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else (tests, benches) sees the real single CPU device.

Mesh shapes (assignment spec):
  single pod:  (16, 16)      axes ("data", "model")       = 256 chips
  multi pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """jax.make_mesh with explicit Auto axis types (SPMD propagation)."""
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes),
                    axis_types=axis_types)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / local runs)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def source_axes(mesh: Mesh) -> Tuple[str, ...]:
    """LP source-partition axes for a mesh: every axis except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """LM batch axes: ('pod','data') on multi-pod, ('data',) otherwise."""
    return tuple(a for a in mesh.axis_names if a != "model")
