"""Production meshes.  Functions (not module constants) so importing never
touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with Auto axis types: the compiler propagates
    shardings from the constraints the model code sets."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over this host's devices: ``data`` x ``model``.  The trainer
    puts its agents on ``data`` (``Trainer(mesh=...)``)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh_auto((n // model_parallel, model_parallel),
                          ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
