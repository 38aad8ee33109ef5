"""KMeans device kernels: k-means++ init and Lloyd iterations.

Second-algorithm coverage (BASELINE.md config 5: "KMeans / LinearRegression
... second-algo stretch"). Same TPU shape as PCA: the hot op is an MXU
matmul (the −2·X·Cᵀ term of the pairwise distances and the one-hot
cluster-sum reduction), iteration is a ``lax.while_loop`` compiled into the
program (no per-iteration host round trip), and the distributed form
all-reduces per-cluster sufficient statistics with ``psum`` — never rows.

All shapes static; padded rows are excluded via ``mask`` everywhere
(assignment, sums, cost).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.obs.xprof import tracked_jit
from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric


class KMeansResult(NamedTuple):
    centers: jnp.ndarray      # (k, n_features)
    cost: jnp.ndarray         # scalar: sum of squared distances (inertia)
    n_iter: jnp.ndarray       # scalar int
    converged: jnp.ndarray    # scalar bool


def _pairwise_sqdist(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """||x−c||² via the expanded form — the cross term is one MXU matmul."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]
    cross = lax.dot_general(
        x, centers, (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST,
    )
    return jnp.maximum(x2 + c2 - 2.0 * cross, 0.0)


def assign_clusters(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmin(_pairwise_sqdist(x, centers), axis=1)


# Serving-path entry point: the standalone jitted assignment with compile
# telemetry (models.KMeansModel.transform). assign_clusters itself stays
# un-jitted so it fuses inside the training-loop programs.
assign_clusters_jit = tracked_jit(assign_clusters, label="kmeans_assign")

# Pipelined-serving variants (KMeansModel.serving_transform_program): the
# *_serve form donates the staged batch buffer (the pipeline never re-reads
# a staged buffer, and its retry path always re-stages from host rows);
# the reduced-precision forms are separate tracked signatures per bucket,
# env-gated + max-error-checked by the serving engine. Cluster assignment
# only needs the argmin ORDER of the distances, so reduced-precision error
# shows up as boundary-row flips, which the engine's mismatch-fraction
# guard bounds.
assign_clusters_serve = tracked_jit(
    assign_clusters, label="kmeans_assign_serve", donate_argnums=(0,)
)


def _assign_bf16(x: jnp.ndarray, centers_bf16: jnp.ndarray) -> jnp.ndarray:
    """bf16 cross-term matmul with f32 accumulation; norms in f32 of the
    SAME bf16-rounded operands so the expanded ||x−c||² stays
    consistent. Centers arrive PRE-CAST (staged once at program build)."""
    xb = x.astype(jnp.bfloat16)
    cross = lax.dot_general(
        xb, centers_bf16, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    xf = xb.astype(jnp.float32)
    cf = centers_bf16.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=1, keepdims=True)
    c2 = jnp.sum(cf * cf, axis=1)[None, :]
    return jnp.argmin(x2 + c2 - 2.0 * cross, axis=1)


assign_clusters_bf16 = tracked_jit(_assign_bf16, label="kmeans_assign_bf16")


def _assign_int8(x: jnp.ndarray, centers_q: jnp.ndarray,
                 centers_scale: jnp.ndarray) -> jnp.ndarray:
    """int8 cross term with int32 accumulation (``ops.quantize``), norms
    of the dequantized operands in f32 — distances consistent with the
    quantized geometry, argmin unchanged under the shared scales.
    Centers arrive PRE-QUANTIZED; only the batch quantizes per call."""
    xq, sx = quantize_symmetric(x)
    cross = lax.dot_general(
        xq, centers_q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32) * (sx * centers_scale)
    xf = xq.astype(jnp.float32) * sx
    cf = centers_q.astype(jnp.float32) * centers_scale
    x2 = jnp.sum(xf * xf, axis=1, keepdims=True)
    c2 = jnp.sum(cf * cf, axis=1)[None, :]
    return jnp.argmin(x2 + c2 - 2.0 * cross, axis=1)


assign_clusters_int8 = tracked_jit(_assign_int8, label="kmeans_assign_int8")

# Un-jitted stage bodies for the fused whole-pipeline serving programs
# (models._serving.build_fused_pipeline_program). Assignment is
# output-typed (labels), so KMeans composes only as the TERMINAL stage.
SERVING_STAGE_BODIES = {
    "native": assign_clusters,
    "bf16": _assign_bf16,
    "int8": _assign_int8,
}


@partial(tracked_jit, static_argnames=("n_clusters",))
def kmeans_plus_plus_init(
    x: jnp.ndarray,
    n_clusters: int,
    key: jax.Array,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """k-means++ seeding on device: next center sampled ∝ min-distance².

    Plays the role of Spark's k-means|| default init — same D²-weighting
    idea, run as a k-step ``fori_loop`` in one compiled program.
    """
    m, n = x.shape
    valid = jnp.ones(m, dtype=x.dtype) if mask is None else mask.astype(x.dtype)
    neg_inf = jnp.asarray(-jnp.inf, dtype=x.dtype)
    key, sub = jax.random.split(key)
    # first center sampled proportionally to the mask value: uniform when
    # the mask is 0/1 validity, and w-proportional when it carries
    # weightCol (the weighted k-means++ first-draw rule)
    first = jax.random.categorical(
        sub, jnp.where(valid > 0, jnp.log(jnp.maximum(valid, 1e-30)),
                       neg_inf)
    )
    centers0 = jnp.zeros((n_clusters, n), dtype=x.dtype).at[0].set(x[first])
    min_d0 = jnp.sum((x - x[first][None, :]) ** 2, axis=1) * valid

    def body(i, state):
        centers, min_d, key = state
        key, sub = jax.random.split(key)
        # sample ∝ D² over VALID rows only — masked (padding) rows must
        # stay -inf even when all valid distances are zero (duplicate-heavy
        # shards), else a zero-filled padding row becomes a center.
        logits = jnp.where(
            valid > 0, jnp.log(jnp.maximum(min_d, 1e-30)), neg_inf
        )
        idx = jax.random.categorical(sub, logits)
        c = x[idx]
        centers = centers.at[i].set(c)
        d_new = jnp.sum((x - c[None, :]) ** 2, axis=1) * valid
        return centers, jnp.minimum(min_d, d_new), key

    centers, _, _ = lax.fori_loop(1, n_clusters, body, (centers0, min_d0, key))
    return centers


def _cluster_stats(x, centers, valid):
    """One Lloyd half-step: assignment + per-cluster (Σx, count, cost).

    The one-hot reduction ``onehotᵀ·X`` is an MXU matmul, not a scatter —
    the TPU-friendly formulation of the cluster sum.
    """
    k = centers.shape[0]
    d = _pairwise_sqdist(x, centers)
    labels = jnp.argmin(d, axis=1)
    onehot = jax.nn.one_hot(labels, k, dtype=x.dtype) * valid[:, None]
    sums = lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), precision=lax.Precision.HIGHEST
    )
    counts = jnp.sum(onehot, axis=0)
    cost = jnp.sum(jnp.min(d, axis=1) * valid)
    return sums, counts, cost


def lloyd_iterations(
    x: jnp.ndarray,
    init_centers: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    max_iter: int,
    tol: float,
    reduce_fn: Callable = lambda t: t,
) -> KMeansResult:
    """Lloyd's algorithm as a ``lax.while_loop``.

    ``reduce_fn`` combines (sums, counts, cost) across shards — identity on
    one device, ``psum`` over the mesh in the distributed path; everything
    else is shared between the two.
    """
    valid = (
        jnp.ones(x.shape[0], dtype=x.dtype) if mask is None else mask.astype(x.dtype)
    )

    def step(state):
        centers, _, it, _ = state
        sums, counts, cost = reduce_fn(_cluster_stats(x, centers, valid))
        # empty cluster: keep its previous center (Spark behavior). Divide
        # by the ACTUAL weight mass, not max(counts, 1): with weightCol
        # routed through the mask slot, a cluster's total weight can be a
        # fraction below 1 and flooring it would shrink the center
        denom = jnp.where(counts > 0, counts, 1.0)[:, None]
        new_centers = jnp.where(counts[:, None] > 0, sums / denom, centers)
        shift2 = jnp.sum((new_centers - centers) ** 2, axis=1)
        moved = jnp.sqrt(jnp.max(shift2))
        return new_centers, cost, it + 1, moved <= tol

    def cond(state):
        _, _, it, done = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(done))

    init_state = (
        init_centers,
        jnp.array(jnp.inf, dtype=x.dtype),
        jnp.array(0, dtype=jnp.int32),
        jnp.array(False),
    )
    centers, _, n_iter, converged = lax.while_loop(cond, step, init_state)
    # final cost under the final centers
    _, _, cost = reduce_fn(_cluster_stats(x, centers, valid))
    return KMeansResult(centers, cost, n_iter, converged)


@partial(tracked_jit, donate_argnums=(0,))
def update_cluster_stats(
    carry,
    centers: jnp.ndarray,
    batch: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
):
    """Out-of-core Lloyd building block: fold one batch's per-cluster
    (Σx, count, cost) into a donated accumulator. One streamed pass with
    this per batch = one Lloyd assignment half-step over the full dataset,
    HBM bounded at one batch + one (k, n) accumulator."""
    sums, counts, cost = carry
    valid = (
        jnp.ones(batch.shape[0], dtype=batch.dtype)
        if mask is None
        else mask.astype(batch.dtype)
    )
    s, c, co = _cluster_stats(batch.astype(sums.dtype), centers, valid)
    # per-batch one-hot counts are exact integers in f32; accumulate in the
    # carry's integer dtype so totals stay exact past 2^24 rows
    return sums + s, counts + c.astype(counts.dtype), cost + co


@tracked_jit
def next_centers(carry, centers: jnp.ndarray):
    """The end of one streamed Lloyd step: (each centre the mean of the rows
    the step gave it — an empty cluster keeps its centre —, the largest
    distance a centre moved). One program, whose scalar the fit reads."""
    sums, counts, _ = carry
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1).astype(sums.dtype)[:, None],
                    centers)
    return new, jnp.sqrt(jnp.max(jnp.sum((new - centers) ** 2, axis=1)))


@tracked_jit
def take_rows(centers: jnp.ndarray, batch: jnp.ndarray, rows: jnp.ndarray,
              take: jnp.ndarray) -> jnp.ndarray:
    """``centers`` with each centre ``i`` where ``take[i]`` replaced by row
    ``rows[i]`` of the device ``batch``: initial centres gathered from the
    batches a streamed fit keeps on the chip. Fixed shapes (k,), whatever
    how many rows a batch gives."""
    return jnp.where(take[:, None], batch[rows].astype(centers.dtype),
                     centers)


def step_reserve_bytes(batch_rows: int, k: int, n_features: int,
                       itemsize: int) -> int:
    """Device bytes a streamed Lloyd step needs beside the batches a fit
    keeps (``ops.streaming.keep_budget_bytes``' reserve): three batches
    (two puts in flight and the one being stepped, where nothing is kept),
    the (rows, k) distances and one-hot, should the compiler hold them (the
    v5e's fuses them away), and four (k, n): the carry's sums, their
    donated successor, the centres and the next centres."""
    return itemsize * (3 * batch_rows * n_features + 2 * batch_rows * k
                       + 4 * k * n_features)


@partial(tracked_jit, static_argnames=("max_iter",))
def kmeans_fit_kernel(
    x: jnp.ndarray,
    init_centers: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    max_iter: int = 20,
    tol: float = 1e-4,
) -> KMeansResult:
    return lloyd_iterations(x, init_centers, mask, max_iter, tol)
