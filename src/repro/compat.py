"""Mesh and pytree helpers, spelled once on JAX 0.9's native APIs.

Everything mesh-aware in this repo (``core.distributed``,
``models.moe``, ``launch.dryrun``, ``sharding.policy``, the shard_map
tests) routes through these names rather than touching
``jax.shard_map`` / ``jax.sharding`` directly.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

#: context manager installing ``mesh`` as the ambient mesh
set_mesh = jax.sharding.set_mesh

#: ``jax.tree.flatten_with_path``
tree_flatten_with_path = jax.tree.flatten_with_path


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` over an explicit mesh."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def get_mesh():
    """The ambient (abstract) mesh, or ``None`` when none is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def make_mesh(shape: tuple, axis_names: tuple, devices) -> jax.sharding.Mesh:
    """Mesh over an explicit device list (e.g. a prefix of the
    host-platform virtual devices), with ``Auto`` axes: sharding stays
    in the compiler's hands, as every caller here expects."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
