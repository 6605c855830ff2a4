"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips over DCN/ICI.

Functions (not module constants) so importing never touches device state.
Every mesh axis is ``Auto``: the models place arrays with sharding
constraints and let the partitioner propagate them, where JAX's default
``Explicit`` axes would demand an output sharding at every gather.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(shape=(2, 16, 16) if multi_pod else (16, 16),
                      axes=("pod", "data", "model") if multi_pod
                      else ("data", "model"))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
