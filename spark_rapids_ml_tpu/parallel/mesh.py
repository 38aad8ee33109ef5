"""Device mesh construction and sharding helpers.

The reference's "cluster" is Spark executors each owning one GPU, with
device assignment via ``spark.executor.resource.gpu`` task resources
(``RapidsRowMatrix.scala:171-175``) and ALL cross-device communication done
by shipping JVM-serialized matrices to the driver
(``RapidsRowMatrix.scala:202``). The TPU-native replacement is a
``jax.sharding.Mesh``: devices are first-class, data is laid out with named
shardings, and XLA compiles the collectives onto ICI/DCN.

Axis convention: ``data`` — rows (samples) are sharded across it; model
state (covariance, components) is replicated. A second ``feature`` axis is
reserved for sharding the n×n Gram when n is too large for one device
(SURVEY.md §5 "feature-dimension scaling" stretch goal).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def device_count() -> int:
    return len(jax.devices())


def data_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """1-D mesh over the ``data`` axis (data-parallel partial aggregation —
    the only parallelism the workload needs for parity, SURVEY.md §2)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"requested {n_devices} devices, {len(devices)} visible"
                )
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def grid_mesh(n_data: int, n_feature: int) -> Mesh:
    """2-D (data × feature) mesh for the sharded-Gram stretch path."""
    devices = jax.devices()
    need = n_data * n_feature
    if need > len(devices):
        raise ValueError(f"requested {need} devices, {len(devices)} visible")
    grid = np.asarray(devices[:need]).reshape(n_data, n_feature)
    return Mesh(grid, (DATA_AXIS, FEATURE_AXIS))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded over ``data``; feature dim replicated."""
    return NamedSharding(mesh, P(DATA_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_shape(mesh: Mesh) -> dict:
    """Axes/shape/device summary for fit reports and logs."""
    first = mesh.devices.flat[0]
    return {
        "axes": tuple(str(a) for a in mesh.axis_names),
        "shape": tuple(int(s) for s in mesh.devices.shape),
        "devices": int(mesh.devices.size),
        "platform": getattr(first, "platform", "unknown"),
    }


def collective_nbytes(shape, dtype) -> int:
    """Payload bytes of one collective operand of ``shape``/``dtype`` —
    the unit every driver's program-level collective accounting
    (``FitContext.record_collective``) is declared in."""
    return int(np.prod([int(s) for s in shape], dtype=np.int64)) * np.dtype(
        dtype
    ).itemsize


def pad_rows_to_multiple(x: np.ndarray, multiple: int):
    """Pad rows so the leading dim divides the mesh; returns (padded, mask).

    XLA shardings need equal per-device extents; uneven partitions are
    padded and masked rather than recompiled (the Spark analogue is
    variable-size partitions, which the reference handles by per-partition
    dynamic shapes — a non-option under jit).
    """
    n = x.shape[0]
    rem = (-n) % multiple
    mask = np.ones(n + rem, dtype=x.dtype if np.issubdtype(x.dtype, np.floating) else np.float64)
    if rem:
        x = np.concatenate([x, np.zeros((rem,) + x.shape[1:], dtype=x.dtype)])
        mask[n:] = 0.0
    return x, mask
