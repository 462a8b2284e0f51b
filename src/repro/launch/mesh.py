"""Mesh construction for the production pods and local tests.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The multi-pod mesh adds a
leading DCN-connected ``pod`` axis that only ever carries data-parallel
all-reduces; all tensor/expert collectives stay intra-pod on ICI — this is
the property that scales the design past 1000 nodes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Best-effort local mesh from however many devices exist."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return _mesh((data, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size) if mesh is not None else 1
