"""The one place meshes are built.

``jax.make_mesh`` now gives every axis the Explicit type, under which
``with_sharding_constraint`` on an axis-sharded activation (the
sequence-parallel residual, ``models.common.sp_constrain``) raises. Every
mesh in this repo is therefore built here with Auto axes: the compiler
propagates shardings from the annotated arguments and constraints.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Mesh of ``shape`` over ``axes`` with Auto axis types.

    Over all visible devices the device order comes from
    ``jax.make_mesh`` (topology-aware); over fewer, it is the first
    devices in ``jax.devices()`` order."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    n = math.prod(shape)
    if n == len(jax.devices()):
        return jax.make_mesh(shape, axes, axis_types=types)
    devices = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devices, axes, axis_types=types)
