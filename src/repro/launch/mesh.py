"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax use).

Production layout (TPU v5e pods):
  single-pod:  (data=16, model=16)            = 256 chips
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips
The "pod" axis carries data parallelism across pods by default (gradient
all-reduce crosses DCN); pipeline parallelism over "pod" is available via
distributed.pipeline for bandwidth-constrained inter-pod links.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto`: shardings are propagated by
    the compiler, as the model and table code expects (jax's default of
    `Explicit` axes makes sharding part of every array's type)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes carrying data parallelism (pod folds into DP when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
