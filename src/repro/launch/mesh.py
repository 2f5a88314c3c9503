"""Production mesh construction (assignment-specified geometry).

A FUNCTION, not a module constant: importing this module never touches jax
device state.  Single pod = (16, 16) chips = ('data','model'); multi-pod
adds a leading 'pod' axis (2 pods = 512 chips)."""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    # every mesh here is GSPMD-Auto: shardings are constraints, not types
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many (virtual) devices tests configured."""
    return _mesh(shape, axes)


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """jax.shard_map with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def batch_axes(mesh) -> tuple:
    """Axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
