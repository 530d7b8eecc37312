"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries the slow inter-pod (DCN) dimension; batch shards over
(pod, data), gradients all-reduce hierarchically.

Defined as FUNCTIONS so importing this module never touches jax device
state (dryrun.py must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """`jax.make_mesh` with every axis `Auto`: the engines place state with
    explicit `NamedSharding`s and run collectives inside `jax.shard_map`
    bodies, so no axis takes part in sharding-in-types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_data_mesh(n_devices: int = None, axis: str = "data"):
    """1-D mesh over ``n_devices`` (default: all local devices) with a
    single data axis — the shape the online engine's sharded delta
    maintenance and the distributed combine-broadcast programs expect."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh((n,), (axis,))


def partition_sharding(mesh, axis: str = "data"):
    """NamedSharding that lays a ``(n_parts, capacity)`` partitioned stat
    table out along ``axis``. With ``n_parts == k * n_devices`` each device
    receives k CONTIGUOUS rows — and because key-range partitions are
    contiguous ranges of the hash space, a device's k partitions form one
    contiguous hash range too (k-partitions-per-device: partition capacity
    is bounded independently of the mesh size). ``n_parts`` must be a
    multiple of the axis size; the partitioned online engine enforces
    that."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(axis, None))


def parts_per_device(mesh, n_parts: int, axis: str = "data") -> int:
    """k = n_parts / axis size (validating divisibility)."""
    n_dev = int(mesh.shape[axis])
    if n_parts % n_dev != 0:
        raise ValueError(f"n_parts={n_parts} not a multiple of the "
                         f"'{axis}' axis size {n_dev}")
    return n_parts // n_dev


def shard_partitions(mesh, tree, axis: str = "data"):
    """Place every (n_parts, ...) array leaf of ``tree`` with
    :func:`partition_sharding` over ``mesh``."""
    import jax as _jax
    s = partition_sharding(mesh, axis)
    return _jax.tree.map(lambda a: _jax.device_put(a, s), tree)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)
