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

from functools import partial
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.obs.xprof import tracked_jit

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


# -- all-reduce of what each chip accumulated alone -------------------------
#
# A streamed fit over several chips (``ops.streaming.stream_covariance``)
# runs one-chip programs on arrays committed to each chip and meets the
# other chips only here. ``sharded_over`` hands the chips' arrays to a mesh
# program as they stand (no copy: chip i's array IS shard i);
# ``on_each_chip`` takes the replicated answer apart again.


def sharded_over(mesh: Mesh, per_chip: Sequence) -> jax.Array:
    """One array sharded over ``data`` on its leading axis whose shard on
    the mesh's i-th device is ``per_chip[i]``, which lives there."""
    first = per_chip[0]
    shape = (len(per_chip) * first.shape[0],) + tuple(first.shape[1:])
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P(DATA_AXIS)), list(per_chip))


def on_each_chip(replicated: jax.Array, devices: Sequence) -> list:
    """The replicas of ``replicated`` on ``devices``, in their order."""
    by_device = {s.device: s.data for s in replicated.addressable_shards}
    return [by_device[d] for d in devices]


@partial(tracked_jit, static_argnames=("mesh",))
def all_reduce_sum(parts, *, mesh: Mesh):
    """Sum of every chip's block of each leaf of ``parts``, on every chip:
    one ``psum`` over ``data`` a leaf."""
    fn = jax.shard_map(lambda t: jax.lax.psum(t, DATA_AXIS), mesh=mesh,
                       in_specs=P(DATA_AXIS), out_specs=P())
    return fn(parts)


@partial(tracked_jit, static_argnames=("mesh",))
def all_reduce_mean(col_sums, counts, *, mesh: Mesh):
    """Every chip's (Σx, n) → the mean and the row count of all the
    chips' rows, on every chip."""

    def shard_fn(s, c):
        total = jax.lax.psum(c, DATA_AXIS)[0]
        return jax.lax.psum(s, DATA_AXIS) / total, total

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=P(DATA_AXIS),
                       out_specs=P())
    return fn(col_sums, counts)


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
