"""Production mesh construction. A FUNCTION, not a module constant — importing this
module must never touch jax device state (smoke tests see 1 device; only the dry-run
forces 512 host devices).

Every axis is ``AxisType.Auto``: ``layers.constrain`` hands GSPMD bare
``PartitionSpec`` hints, which jax rejects on the ``Explicit`` axes
``jax.make_mesh`` defaults to."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Whatever this host actually has — used by the training launcher."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))
