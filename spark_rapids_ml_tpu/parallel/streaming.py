"""Distributed out-of-core PCA: streamed batches over a device mesh.

The north-star config (BASELINE.md #4: 10M×4096 over a multi-chip slice)
needs BOTH halves at once: rows too many for host/HBM (stream them) and
chips to spread them over (shard them). This module combines
``ops/streaming.py``'s donated accumulator with ``distributed_pca.py``'s
mesh layout:

* the accumulator keeps a PER-DEVICE leading axis — ``gram (D, n, n)``,
  ``col_sum (D, n)``, ``count (D,)`` — sharded over the ``data`` axis, so a
  batch update is pure local compute on every chip (NO collective per
  batch; the reference's analogue shipped one n×n partial per partition to
  the driver, ``RapidsRowMatrix.scala:168-202``);
* each incoming (B, n) host batch is placed row-sharded (B/D rows per
  chip) and folded into that chip's slice of the accumulator via a single
  donated jitted program;
* ``finalize`` runs ONE collective: the sum over the device axis (XLA
  partitions it into an all-reduce over ICI), then covariance → eigh →
  postprocess replicated.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.obs import (
    current_fit,
    current_run,
    fit_instrumentation,
    tracked_jit,
)
from spark_rapids_ml_tpu.ops.covariance import covariance_from_stats, partial_gram_stats
from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance
from spark_rapids_ml_tpu.ops.pca_kernel import PCAFitResult
from spark_rapids_ml_tpu.ops.streaming import GramStats
from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    collective_nbytes,
    row_sharding,
)


@partial(tracked_jit, static_argnames=("mesh",), donate_argnums=(0,))
def update_stats_sharded(
    stats: GramStats, batch: jnp.ndarray, mask: jnp.ndarray, *, mesh: Mesh
) -> GramStats:
    """Fold one row-sharded batch into the per-device accumulator slices.

    Local compute only — each device updates its own (1, n, n) block; the
    cross-device reduction is deferred to ``finalize_stats_sharded``.
    """

    def shard_fn(g, s, c, b, m):
        pg, ps, pc = partial_gram_stats(b.astype(g.dtype), m)
        return g + pg[None], s + ps[None], c + pc[None]

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS, None, None),
            P(DATA_AXIS, None),
            P(DATA_AXIS),
            P(DATA_AXIS, None),
            P(DATA_AXIS),
        ),
        out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None), P(DATA_AXIS)),
    )
    g, s, c = fn(stats.gram, stats.col_sum, stats.count, batch, mask)
    return GramStats(g, s, c)


@partial(
    tracked_jit, static_argnames=("k", "mean_centering", "flip_signs", "solver")
)
def finalize_stats_sharded(
    stats: GramStats, k: int, mean_centering: bool = True,
    flip_signs: bool = True, solver: str = "eigh",
) -> PCAFitResult:
    """One all-reduce (the axis-0 sum over sharded slices), then the same
    covariance → eigh → postprocess chain as every other fit path."""
    g = jnp.sum(stats.gram, axis=0)
    s = jnp.sum(stats.col_sum, axis=0)
    cnt = jnp.sum(stats.count, axis=0)
    cov = covariance_from_stats(g, s, cnt, mean_centering=mean_centering)
    mean = s / cnt if mean_centering else jnp.zeros_like(s)
    components, evr = pca_from_covariance(
        cov, k, flip_signs=flip_signs, solver=solver
    )
    return PCAFitResult(components, evr, mean)


class DistributedStreamingPCA:
    """``DistributedStreamingPCA(n, mesh).partial_fit(b)....finalize(k)`` —
    bounded HBM per chip AND data-parallel scale-out in one accumulator."""

    def __init__(self, n_features: int, mesh: Mesh, dtype=jnp.float32):
        self._mesh = mesh
        self._n = n_features
        d = mesh.devices.size
        shard3 = NamedSharding(mesh, P(DATA_AXIS, None, None))
        shard2 = NamedSharding(mesh, P(DATA_AXIS, None))
        shard1 = NamedSharding(mesh, P(DATA_AXIS))
        # allocated sharded: each chip zero-fills its own (1, n, n) slice
        # instead of device 0 building all D of them and scattering
        self._stats = GramStats(
            gram=jnp.zeros((d, n_features, n_features), dtype=dtype,
                           device=shard3),
            col_sum=jnp.zeros((d, n_features), dtype=dtype, device=shard2),
            count=jnp.zeros((d,), dtype=jnp.int32, device=shard1),
        )

    def partial_fit(self, batch, mask=None) -> "DistributedStreamingPCA":
        batch = np.asarray(batch)
        d = self._mesh.devices.size
        if batch.shape[0] % d:
            raise ValueError(
                f"batch rows {batch.shape[0]} must divide evenly over the "
                f"{d}-device mesh (pad + mask the tail)"
            )
        if mask is None:
            mask = np.ones((batch.shape[0],), dtype=bool)
        x_dev = jax.device_put(batch, row_sharding(self._mesh))
        m_dev = jax.device_put(
            np.asarray(mask), NamedSharding(self._mesh, P(DATA_AXIS))
        )
        self._stats = update_stats_sharded(
            self._stats, x_dev, m_dev, mesh=self._mesh
        )
        return self

    @property
    def rows_seen(self) -> int:
        return int(np.asarray(jnp.sum(self._stats.count)))

    @property
    def rows_per_device(self) -> dict:
        """Rows each chip has accumulated, read from its own slice of the
        sharded count — ``{device label: rows}``."""
        return {str(s.device): int(np.asarray(s.data).sum())
                for s in self._stats.count.addressable_shards}

    def finalize(
        self, k: int, mean_centering: bool = True, solver: str = "eigh"
    ) -> PCAFitResult:
        # the ONE collective of the streamed fit: the axis-0 sum over the
        # per-device (gram, col_sum, count) slices
        n = self._n
        current_fit().record_collective(
            "all_reduce",
            nbytes=collective_nbytes((n * n + n + 1,),
                                     self._stats.gram.dtype),
        )
        return jax.block_until_ready(
            finalize_stats_sharded(
                self._stats, k, mean_centering=mean_centering, solver=solver
            )
        )


@fit_instrumentation("distributed_streaming_pca")
def distributed_streaming_pca_fit(
    source,
    k: int,
    mesh: Mesh,
    mean_centering: bool = True,
    dtype=jnp.float32,
    solver: str = "eigh",
) -> PCAFitResult:
    """Out-of-core fit of a ``data.batches.BatchSource`` over a mesh.

    The source's fixed batch shape is rounded to the mesh size by
    construction (``BatchSource`` pads + masks its tail), so every batch
    update hits one cached executable per chip.
    """
    d = mesh.devices.size
    if source.batch_rows % d:
        raise ValueError(
            f"source batch_rows {source.batch_rows} must be a multiple of "
            f"the mesh size {d}"
        )
    ctx = current_fit()
    acc = DistributedStreamingPCA(source.n_features, mesh, dtype=dtype)
    host_dtype = np.dtype(jnp.zeros((), dtype=dtype).dtype.name)
    n_batches = 0
    with ctx.phase("stream"):
        for batch, mask in source.batches():
            # accumulator updates pipeline on device — each fold's step
            # measures the host-side fold time (placement + dispatch)
            with current_run().step(
                "stream_fold", rows=batch.shape[0]
            ) as mon:
                acc.partial_fit(
                    batch.astype(host_dtype, copy=False), mask)
                mon.note(fold=float(n_batches))
            n_batches += 1
    ctx.set_data(rows=acc.rows_seen, features=source.n_features)
    ctx.note(batches_streamed=n_batches, rows_per_device=acc.rows_per_device)
    if mean_centering and acc.rows_seen < 2:
        raise ValueError("mean centering requires more than one row")
    with ctx.phase("finalize"), current_run().step(
        "finalize", rows=acc.rows_seen
    ):
        return acc.finalize(k, mean_centering=mean_centering, solver=solver)
