"""Test mesh construction for the distributed decode tests.

`make_test_mesh` builds an 8-device (data, model) mesh, or a
(pod, data, model) one, on forced host devices; `data_axis_size` is the
number of data-parallel replicas of such a mesh.

Meshes are built through `runtime.jaxcompat.make_mesh` (``AxisType.Auto`` on
every axis), the one call site for jax's mesh API.

Functions, not module-level constants: importing this module never touches
jax device state (callers set XLA_FLAGS before any jax import).
"""

from __future__ import annotations

from repro.runtime.jaxcompat import make_mesh


def make_test_mesh(*, multi_pod: bool = False):
    """8 devices: (4, 2) data x model, or (2, 2, 2) pod x data x model."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axis_size(mesh) -> int:
    size = mesh.shape["data"]
    if "pod" in mesh.shape:
        size *= mesh.shape["pod"]
    return size


__all__ = ["make_test_mesh", "data_axis_size"]
