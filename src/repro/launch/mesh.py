"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 256 chips as (data=16, model=16).
Multi-pod: 2 pods x 256 chips as (pod=2, data=16, model=16); the ``pod``
axis carries only data-parallel gradient all-reduce (DCN-friendly).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: sharding constraints inside jit may name any mesh axis
    # (make_mesh defaults to Explicit axes, which reject them)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """(data, model) mesh over the devices of this host."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def data_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes_of(mesh) -> tuple:
    return ("model",)
