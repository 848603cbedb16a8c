"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; tests and benchmarks see the real (single) device.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.experimental import mesh_utils


def make_mesh(shape, axes):
    """``jax.make_mesh`` over every device with ``Auto`` axis types: the
    shardings here are explicit ``PartitionSpec``s the compiler resolves
    (``jax.make_mesh`` now defaults to ``Explicit``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the locally-available devices (tests / examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))


def shard_axis_names(axis: str, ndim: int) -> Tuple[str, ...]:
    """Axis names of a shard mesh: the single historical ``axis`` for 1-D,
    ``(axis_x, axis_y[, axis_z])`` for multi-axis meshes."""
    if ndim == 1:
        return (axis,)
    return tuple(f"{axis}_{d}" for d in ("x", "y", "z")[:ndim])


def make_shard_mesh(n_shards: Optional[Union[int, Tuple[int, ...]]] = None,
                    axis: str = "shard"):
    """Mesh for the asynchronous shard runtime (runtime/shard_runtime.py):
    one block owner per device.

    ``n_shards`` is an int (the historical 1-D pencil mesh along ``axis``)
    or a mesh shape tuple ``(px,)``/``(px, py)``/``(px, py, pz)`` laying
    ``prod(shape)`` devices row-major over axes ``shard_axis_names(axis,
    ndim)`` — the shape ``ShardRuntimeConfig.mesh_shape`` declares and
    ``solvers.partition.MeshPartition`` tiles the grid by.

    Unlike the production meshes this may use a *prefix* of the available
    devices (a 2-shard runtime on a 4-device host is a valid experiment),
    so it builds ``jax.sharding.Mesh`` directly instead of going through
    ``make_mesh`` — which binds every device.  A 1-D mesh over all devices
    takes ``mesh_utils.create_device_mesh``'s ring order, which follows the
    physical links: on a 2x2 TPU host the ring 0-1-3-2 steps only between
    linked chips, where id order would make two hops diagonal.  Multi-axis
    meshes keep id order, which on that host is the physical 2x2 grid.
    """
    devices = jax.devices()
    if n_shards is None:
        shape: Tuple[int, ...] = (len(devices),)
    elif isinstance(n_shards, (tuple, list)):
        shape = tuple(int(s) for s in n_shards)
        if not 1 <= len(shape) <= 3:
            raise ValueError(f"mesh shape {shape} must be 1-D, 2-D, or 3-D")
    else:
        shape = (int(n_shards),)
    if any(s < 1 for s in shape):
        raise ValueError(f"n_shards={shape} must be >= 1 per axis")
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"n_shards={shape} needs {n} devices, which exceeds the "
            f"{len(devices)} available (set XLA_FLAGS=--xla_force_host_"
            "platform_device_count before the first jax import to emulate "
            "more)")
    names = shard_axis_names(axis, len(shape))
    if len(shape) == 1 and n == len(devices):
        grid = mesh_utils.create_device_mesh(shape, devices)
    else:
        grid = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(grid, names)


def shard_axis_of(mesh) -> str:
    """The (single) axis of a shard-runtime mesh."""
    if len(mesh.axis_names) != 1:
        raise ValueError(f"expected a 1-D shard mesh, got axes {mesh.axis_names}")
    return mesh.axis_names[0]


def shard_axes_of(mesh) -> Tuple[str, ...]:
    """All shard axes of a (possibly multi-axis) shard-runtime mesh, in
    grid-axis order."""
    return tuple(mesh.axis_names)


def dp_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")
