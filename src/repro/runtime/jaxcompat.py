"""The one call site for the jax mesh / shard_map APIs (written for jax 0.9.0).

The API surfaces the distributed layer depends on have moved between jax
releases before, and every call site used to hardcode one spelling — which is
how the whole subsystem once went dark.  Routing them through this module
keeps the next move a one-file change (flashlint FL001 enforces it):

  * ``shard_map`` — ``jax.shard_map``, replication check ``check_vma``.
  * ``make_mesh`` — ``jax.make_mesh`` with ``axis_types=(AxisType.Auto, ...)``.
  * ``abstract_mesh`` — ``jax.sharding.AbstractMesh(axis_sizes, axis_names)``.
  * ``is_traced`` — ``isinstance(x, jax.core.Tracer)``.

Importing this module never touches jax device state (the dry-runs set
``XLA_FLAGS`` before the first device query, and must keep working).
"""

from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = False):
    """`jax.shard_map` with the replication check named portably.

    ``check_replication`` maps onto ``check_vma``; it defaults False here
    because the distributed decoders combine shards with explicit collectives
    (pmax / all_gather) whose replication the static checker cannot always
    prove.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_replication)


def is_traced(x) -> bool:
    """Whether `x` is an abstract value being traced (inside jit or
    shard_map) rather than a concrete array."""
    return isinstance(x, jax.core.Tracer)


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """Device-free `AbstractMesh`.

    Use this to describe a *target* topology (e.g. for elastic-rescale
    planning) on hosts that do not have the devices — only axis names and
    sizes are recorded.
    """
    axis_sizes = tuple(int(s) for s in axis_sizes)
    axis_names = tuple(axis_names)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"axis_sizes {axis_sizes} and axis_names {axis_names} disagree")
    return AbstractMesh(axis_sizes, axis_names)


__all__ = ["shard_map", "make_mesh", "abstract_mesh"]
