"""Distributed out-of-core PCA: streamed batches over a device mesh.

The north-star config (BASELINE.md #4: 10M×4096 over a multi-chip slice)
needs BOTH halves at once: rows too many for host/HBM (stream them) and
chips to spread them over (shard them). There is one streamed fit body,
``ops.streaming.stream_covariance``, and it takes the chips: the slices
that cross (host batches, cut at 1 GiB) are dealt to them in turn, each
chip folds its own into its own
accumulator with the one-chip programs (NO collective per batch; the
reference's analogue shipped one n×n partial per partition to the driver,
``RapidsRowMatrix.scala:168-202``), and the chips meet in all-reduces over
ICI only — the mean after pass 1, then the Grams (summed in pass 1 about
each chip's first batch's mean and re-centred; pass 2's where the rows
refuse that). The eigensolve
then runs once, on one chip, outside any mesh program.

``distributed_streaming_pca_fit`` is that loop under a mesh-shaped
signature (``PCA().set("numDevices", d).fit`` is the estimator's door to
it); ``DistributedStreamingPCA`` is the same per-chip state for callers
that feed batches as they come and finalize more than once
(``serve.rollout.StreamingTrainer``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from spark_rapids_ml_tpu.obs import (
    current_fit,
    current_run,
    fit_instrumentation,
)
from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance_gated
from spark_rapids_ml_tpu.ops.pca_kernel import PCAFitResult
from spark_rapids_ml_tpu.ops.streaming import (
    GramStats,
    IngestTrace,
    collective_stats,
    finalize_stats,
    init_stats,
    stream_covariance,
    update_stats_auto,
)


class DistributedStreamingPCA:
    """``DistributedStreamingPCA(n, mesh).partial_fit(b)....finalize(k)`` —
    the streamed loop's per-chip one-pass state, fed by hand: bounded HBM
    per chip AND data-parallel scale-out in one accumulator."""

    def __init__(self, n_features: int, mesh: Mesh, dtype=jnp.float32):
        self._ingest = IngestTrace(device=tuple(mesh.devices.flat))
        self._dtype = np.dtype(jnp.zeros((), dtype=dtype).dtype.name)
        # each chip zero-fills its own accumulator
        self._stats = [init_stats(n_features, dtype=dtype, device=d)
                       for d in self._ingest.devices]

    def partial_fit(self, batch, mask=None) -> "DistributedStreamingPCA":
        batch = np.asarray(batch)
        d = len(self._stats)
        # a batch goes to one chip whole, so nothing is split any more; the
        # contract stays, so a caller's batches fit whatever mesh they meet
        if batch.shape[0] % d:
            raise ValueError(
                f"batch rows {batch.shape[0]} must divide evenly over the "
                f"{d}-device mesh (pad + mask the tail)"
            )
        c, x_dev, m_dev = self._ingest.put(
            batch, None if mask is None else np.asarray(mask), self._dtype)
        self._stats[c] = update_stats_auto(self._stats[c], x_dev, m_dev)
        return self

    @property
    def rows_seen(self) -> int:
        return sum(self.rows_per_device.values())

    @property
    def rows_per_device(self) -> dict:
        """Rows each chip has accumulated, read from its own count —
        ``{device label: rows}``."""
        return {str(d): int(s.count)
                for d, s in zip(self._ingest.devices, self._stats)}

    def _total(self) -> GramStats:
        if len(self._stats) == 1:
            return self._stats[0]
        return collective_stats(self._ingest, self._stats)

    def finalize(
        self, k: int, mean_centering: bool = True, solver: str = "eigh"
    ) -> PCAFitResult:
        # the ONE collective of the hand-fed fit, then the one-chip solve;
        # the per-chip accumulators stay as they are (finalize does not end
        # the stream)
        result = jax.block_until_ready(
            finalize_stats(
                self._total(), k, mean_centering=mean_centering,
                solver=solver
            )
        )
        # every batch fed so far has been summed: the put window's last two
        # a chip need not stay on it until the next ``partial_fit``
        self._ingest.release()
        return result


@fit_instrumentation("distributed_streaming_pca")
def distributed_streaming_pca_fit(
    source,
    k: int,
    mesh: Mesh,
    mean_centering: bool = True,
    dtype=jnp.float32,
    solver: str = "eigh",
) -> PCAFitResult:
    """Out-of-core fit of a ``data.batches.BatchSource`` over a mesh's
    chips through ``stream_covariance``: for a re-iterable source that is
    centred two all-reduces and one walk of the rows (a second walk and a
    third all-reduce where the rows refuse the shifted Gram), one of each
    otherwise. The solve
    (``solver``, through the residual gate as in ``PCA.fit``) is one
    program on the first chip.
    """
    d = mesh.devices.size
    if source.batch_rows % d:  # the same contract as ``partial_fit``'s
        raise ValueError(
            f"source batch_rows {source.batch_rows} must be a multiple of "
            f"the mesh size {d}"
        )
    ctx = current_fit()
    ingest = IngestTrace(ctx.timer, tuple(mesh.devices.flat))
    with ctx.phase("stream"), current_run().step("stream"):
        cov, mean, count = stream_covariance(
            source, mean_centering=mean_centering, dtype=dtype, ingest=ingest)
        rows = int(count)
    ctx.note(rows_per_device={chip["device"]: chip["rows"]
                              for chip in ingest.counters["per_chip"]})
    if mean_centering and rows < 2:
        raise ValueError("mean centering requires more than one row")
    with ctx.phase("finalize"), current_run().step("finalize", rows=rows):
        components, evr, _ = pca_from_covariance_gated(cov, k, solver=solver)
        jax.block_until_ready((components, evr))
    ingest.all_landed()  # every batch put is in the covariance solved
    return PCAFitResult(components, evr, mean)
