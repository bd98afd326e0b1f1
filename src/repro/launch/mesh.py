"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never touches
jax device state. Single pod: (16, 16) = 256 chips ('data', 'model'); multi-pod
adds the leading 'pod' axis: (2, 16, 16) = 512 chips. The ('pod', 'data') axes
are the paper's workers; 'model' carries TP/EP/SP.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """jax.make_mesh with every axis Auto (jax's default is Explicit): the
    trainers take the worker axes manual in shard_map and leave the rest to
    GSPMD, steered by the models' sharding hints (all of them manual under
    the pallas backend: ``core.engine.manual_axes``)."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def worker_axes_of(mesh) -> tuple:
    """The paper's 'worker' axes for a production mesh."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_host_mesh(data: int = 4, model: int = 2):
    """Small mesh for host-device tests (8 forced CPU devices)."""
    return make_mesh((data, model), ("data", "model"))
