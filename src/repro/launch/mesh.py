"""Production mesh construction (TPU v5e target).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# jax's ``device_kind`` of the chip the production mesh is built from (a
# TPU v5e); dry-run records name it so the roofline can look up its peaks
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mesh(shape: tuple, axes: tuple):
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which jax.make_mesh's default Explicit
    # axes reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, multi_pod: bool = False):
    """Tiny mesh for CI-scale dry-run tests (requires >= data*model devices,
    e.g. via XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    if multi_pod:
        return _mesh((2, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def num_clients(mesh) -> int:
    """FL clients = pod x data slices (DESIGN.md §5)."""
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return int(n)


def batch_axes(mesh) -> tuple:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def grid_axes(mesh) -> tuple:
    """Mesh axes a flattened sweep grid shards over (fl.placement,
    DESIGN.md §Placement).  Fleet cells are independent programs, so the
    whole mesh — every axis, pods included — serves as one flat pool of
    cell slots."""
    return tuple(mesh.axis_names)
