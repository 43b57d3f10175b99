"""Mesh construction: production (single-pod 16×16, multi-pod 2×16×16) and
arbitrary small meshes for tests, examples and one-host runs.

Every mesh asks for ``AxisType.Auto`` on every axis.  ``jax.make_mesh``
defaults to ``Explicit`` axes, under which sharding is part of each array's
type and must be spelled out at every op; the model code is written for
``Auto`` propagation (GSPMD infers intermediate shardings from the
``constrain`` hints), and under ``Explicit`` the embedding gather puts the
``data`` axis on two dims and raises ``DuplicateSpecError``.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import and only then builds the mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """Mesh of ``shape`` over ``axes`` (e.g. ``((1,), ("data",))``), every
    axis ``Auto``.  ``devices`` defaults to the first ``prod(shape)`` of
    ``jax.devices()``."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
