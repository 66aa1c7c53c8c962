"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod axis
carries only data parallelism / ZeRO reduce-scatter (DCI-friendly); no TP
collective crosses pods.

Meshes are built through `runtime.jaxcompat.make_mesh` (``AxisType.Auto`` on
every axis), the one call site for jax's mesh API.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

from repro.runtime.jaxcompat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small-device-count analogue for CI (8 fake devices)."""
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axis_size(mesh) -> int:
    size = mesh.shape["data"]
    if "pod" in mesh.shape:
        size *= mesh.shape["pod"]
    return size


__all__ = ["make_production_mesh", "make_test_mesh", "data_axis_size"]
