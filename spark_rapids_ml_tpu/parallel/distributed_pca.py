"""Distributed PCA fit: sharded partial Gram + on-device all-reduce.

The reference's distributed covariance ships one n×n double matrix per
partition to the driver and sums there — O(P·n²) driver work over Spark RPC
(``/root/reference/src/main/scala/org/apache/spark/ml/linalg/distributed/RapidsRowMatrix.scala:168-202``).
Here the whole thing is ONE compiled XLA program over a ``Mesh``: each
device computes its shard's sufficient statistics (Gram, column sum, row
count) in HBM, a fused ``psum`` all-reduces them over ICI, and the (small)
eigensolve runs replicated — partials never touch the host.

Two communication schedules:

* ``two_pass`` (default): psum the column sums first, center each shard by
  the global mean, then psum the centered Gram. Matches the reference's
  mean-then-Gram semantics bit-for-bit; 2 collectives.
* ``one_pass``: single fused psum of (Σxxᵀ, Σx, n), covariance via
  ``G − n·μμᵀ``; 1 collective, preferable cross-slice (DCN) where latency
  dominates. Requires HIGHEST-precision accumulation at f32.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops.covariance import (
    covariance_from_stats,
    gram,
    partial_gram_stats,
)
from spark_rapids_ml_tpu.obs import (
    current_fit,
    current_run,
    fit_instrumentation,
    tracked_jit,
)
from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance
from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    collective_nbytes,
    pad_rows_to_multiple,
    row_sharding,
)


class DistributedPCAResult(NamedTuple):
    components: jnp.ndarray
    explained_variance: jnp.ndarray
    mean: jnp.ndarray


def _shard_fit(x_shard, mask_shard, *, k, mean_centering, one_pass, flip_signs,
               solver):
    """Per-device program (runs under shard_map over the ``data`` axis)."""
    dtype = x_shard.dtype
    if one_pass:
        g, s, cnt = partial_gram_stats(x_shard, mask_shard)
        # ONE fused all-reduce over ICI for all three statistics.
        g, s, cnt = jax.lax.psum((g, s, cnt), DATA_AXIS)
        cov = covariance_from_stats(g, s, cnt, mean_centering=mean_centering)
        mean = s / cnt if mean_centering else jnp.zeros_like(s)
    else:
        m = mask_shard[:, None].astype(dtype)
        local_sum = jnp.sum(x_shard * m, axis=0)
        local_cnt = jnp.sum(mask_shard).astype(dtype)
        # collective 1: global mean
        total_sum, cnt = jax.lax.psum((local_sum, local_cnt), DATA_AXIS)
        mean = total_sum / cnt if mean_centering else jnp.zeros_like(total_sum)
        # center + fold 1/√(n−1) into the rows BEFORE the Gram, the
        # reference's trick (RapidsRowMatrix.scala:169,179-181) — partial
        # Grams then sum directly to the covariance.
        scale = 1.0 / jnp.sqrt(jnp.maximum(cnt - 1.0, 1.0))
        xc = (x_shard - mean[None, :]) * m * scale
        # collective 2: all-reduce of partial covariance
        cov = jax.lax.psum(gram(xc), DATA_AXIS)
    components, evr = pca_from_covariance(
        cov, k, flip_signs=flip_signs, solver=solver
    )
    return components, evr, mean


@partial(
    tracked_jit,
    static_argnames=(
        "mesh", "k", "mean_centering", "one_pass", "flip_signs", "solver"
    ),
)
def distributed_pca_fit_kernel(
    x: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    mesh: Mesh,
    k: int,
    mean_centering: bool = True,
    one_pass: bool = False,
    flip_signs: bool = True,
    solver: str = "eigh",
) -> DistributedPCAResult:
    """The full sharded fit as one jitted program.

    ``x``/``mask`` may live on host or be pre-sharded; the in_specs place
    rows over the ``data`` axis, outputs are replicated. ``solver`` is
    ``ops.eigh.pca_from_covariance``'s, as in
    ``distributed_streaming_pca_fit``: the eigensolve is part of this
    program, so ``"eigh"`` at n=4096 makes its first call compile for
    minutes on a TPU (PERF.md) where ``"randomized"`` takes seconds.
    """
    fn = jax.shard_map(
        partial(
            _shard_fit,
            k=k,
            mean_centering=mean_centering,
            one_pass=one_pass,
            flip_signs=flip_signs,
            solver=solver,
        ),
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
    )
    components, evr, mean = fn(x, mask)
    return DistributedPCAResult(components, evr, mean)


@fit_instrumentation("distributed_pca")
def distributed_pca_fit(
    x_host: np.ndarray,
    k: int,
    mesh: Mesh,
    mean_centering: bool = True,
    one_pass: bool = False,
    flip_signs: bool = True,
    dtype=None,
    solver: str = "eigh",
) -> DistributedPCAResult:
    """Host-side driver: pad rows to the mesh, place shards, run the kernel.

    This is what replaces the reference's mapPartitions + driver reduce: the
    host only pads and hands XLA a sharded array; all math and communication
    is on-device.
    """
    ctx = current_fit()
    n_dev = mesh.devices.size
    x_host = np.asarray(x_host)
    if k > x_host.shape[1]:
        raise ValueError(
            f"k = {k} must be at most the number of features {x_host.shape[1]}"
        )
    with ctx.phase("prepare"):
        x_padded, mask = pad_rows_to_multiple(x_host, n_dev)
        if dtype is not None:
            x_padded = x_padded.astype(dtype)
            mask = mask.astype(dtype)
    with ctx.phase("placement"):
        sharding = row_sharding(mesh)
        x_dev = jax.device_put(x_padded, sharding)
        mask_dev = jax.device_put(mask, NamedSharding(mesh, P(DATA_AXIS)))
        # what each chip actually holds — the report's evidence that the
        # rows were split over the mesh and not parked on the first device
        ctx.note(rows_per_device={
            str(s.device): int(s.data.shape[0])
            for s in x_dev.addressable_shards})
    n = x_host.shape[1]
    dt = x_padded.dtype
    if one_pass:
        # ONE fused psum of (Gram, column sum, count)
        ctx.record_collective(
            "all_reduce", nbytes=collective_nbytes((n * n + n + 1,), dt)
        )
    else:
        # psum of (column sum, count), then psum of the centered Gram
        ctx.record_collective(
            "all_reduce", nbytes=collective_nbytes((n + 1,), dt)
        )
        ctx.record_collective(
            "all_reduce", nbytes=collective_nbytes((n, n), dt)
        )
    with ctx.phase("execute"), current_run().step(
        "covariance_eigh", rows=x_host.shape[0]
    ) as step:
        result = jax.block_until_ready(
            distributed_pca_fit_kernel(
                x_dev,
                mask_dev,
                mesh=mesh,
                k=k,
                mean_centering=mean_centering,
                one_pass=one_pass,
                flip_signs=flip_signs,
                solver=solver,
            )
        )
        step.note(k=k, one_pass=int(one_pass))
        return result
