"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import.

Axis semantics (DESIGN.md section 5):
  pod    — slowest axis (data-center interconnect between pods); only gradient
           all-reduce and fully-sharded param axes touch it
  data   — batch / FSDP axis within a pod
  model  — tensor / expert / memory-shard axis (fastest, ICI)
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """The one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which a jitted
    step must run inside ``jax.set_mesh``; the sharded paths here place
    arrays through ``NamedSharding``/``shard_map`` against the mesh that
    ``repro.dist.context.use_mesh`` installs, which needs ``Auto`` axes.
    ``devices`` defaults to ``jax.devices()``; pass a topology's described
    devices to compile for a chip that is not attached."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the full axis-name set (CPU tests)."""
    return make_mesh((1, 1, 1), ("pod", "data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes: ('pod','data') when pod exists, else ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def n_chips(mesh) -> int:
    return int(np.prod(mesh.devices.shape))
