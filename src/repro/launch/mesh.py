"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; only launch/dryrun.py forces the 512 host-device platform.

Every mesh here has ``Auto`` axes: ``jax.make_mesh`` defaults to
``Explicit`` axes, which the model's ``with_sharding_constraint`` layout
hints (models/hints.py) may not name.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (degraded/elastic shapes, e.g. (15, 16))."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def stage_submeshes(plan, devices=None):
    """Two disjoint (data, model) submeshes for a two-stage EE deployment
    (core.stage_mesh.StageMeshPlan), defaulting to the local device set —
    the launch-layer entry the serve driver and examples build their
    ``StagePlacement`` from."""
    from repro.core.stage_mesh import make_stage_meshes
    return make_stage_meshes(jax.devices() if devices is None else devices,
                             plan)


def batch_axes(mesh) -> Tuple[str, ...]:
    """Axes the global batch shards over: ('pod','data') when a pod axis
    exists, else ('data',)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
