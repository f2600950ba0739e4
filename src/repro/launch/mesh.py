"""Production mesh definition (the brief's contract).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips.

``make_compat_mesh`` is the one mesh constructor every caller routes
through, so all meshes share one axis-type policy.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_compat_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices, every axis
    ``AxisType.Auto`` (shardings are propagated, not explicit)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(shape), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_compat_mesh(shape, axes)


def make_gp_mesh(*, multi_pod: bool = False):
    """The GP map-reduce uses every chip as a data shard (the paper's 1-D
    decomposition); same device fleet, flat data axis factored per pod."""
    return make_production_mesh(multi_pod=multi_pod)


def gp_data_axes(mesh) -> tuple[str, ...]:
    """GP shards n over ALL mesh axes (512-way at multi-pod)."""
    return tuple(mesh.axis_names)
