"""Meshes, all built by :func:`make_mesh`.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod axis
is an outer data-parallel dimension with hierarchical (pod-local first)
gradient reduction; it is also the committer/endorser role-split axis for
the fabric engine (core/roles in DESIGN.md §5).

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes=("data", "model"), *, devices=None):
    """The one mesh constructor of the repo. Every axis is
    ``AxisType.Auto``: the step programs rely on sharding propagation, and
    JAX >= 0.9 makes ``jax.make_mesh`` axes Explicit unless told otherwise
    (explicit axes put ``data`` into the step outputs' types, which then
    no longer vmap with host-built arrays). ``devices`` defaults to the
    first ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names of a mesh (pod folds into data)."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))


def dp_size(mesh) -> int:
    s = 1
    for n in dp_axes(mesh):
        s *= mesh.shape[n]
    return s


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)
