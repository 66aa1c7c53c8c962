"""Elastic rescaling: move a training state between mesh shapes.

A checkpoint written on one mesh restores onto any other mesh (the manager
stores unsharded host arrays; `reshard` device_puts them under the new
topology's specs).  `plan_rescale` validates that the new mesh still divides
every sharded axis — the guard a 1000-node scheduler calls before committing
a shrink/grow."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.runtime.jaxcompat import abstract_mesh


def abstract_target_mesh(axis_sizes, axis_names):
    """Describe a rescale *target* topology without owning its devices.

    `plan_rescale` only reads ``mesh.shape``, so a scheduler planning a
    shrink/grow on a login host passes the result of this instead of a real
    `Mesh`.  Goes through `runtime.jaxcompat`, the one call site for
    `AbstractMesh`'s constructor (it has changed between jax releases).
    """
    return abstract_mesh(axis_sizes, axis_names)


def reshard(tree, mesh: Mesh, spec_tree):
    """device_put every leaf under (mesh, spec)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, spec_tree, is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


def plan_rescale(shape_tree, spec_tree, mesh: Mesh) -> list[str]:
    """Return a list of violations (empty = the rescale is legal)."""
    problems: list[str] = []

    def visit(path, shape, spec):
        dims = tuple(spec) if spec is not None else ()
        for i, ax in enumerate(dims):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if i >= len(shape) or shape[i] % total:
                problems.append(
                    f"{path}: dim {i} of {shape} not divisible by {ax}={total}")

    def walk(path, shapes, specs):
        if isinstance(shapes, dict):
            for k in shapes:
                walk(f"{path}/{k}", shapes[k], specs[k])
        elif isinstance(shapes, (list, tuple)):
            for i, (sh, sp) in enumerate(zip(shapes, specs)):
                walk(f"{path}[{i}]", sh, sp)
        else:
            visit(path, shapes.shape if hasattr(shapes, "shape") else shapes,
                  specs)

    walk("", shapes=shape_tree, specs=spec_tree)
    return problems


__all__ = ["reshard", "plan_rescale", "abstract_target_mesh"]
