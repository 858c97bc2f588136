"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The dry-run creates 512
placeholder host devices via XLA_FLAGS before any jax import (dryrun.py
lines 1-2); real deployments get the same topology from the TPU runtime.
"""
from __future__ import annotations

from repro.distributed.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
