"""KMeans Estimator / Model with the Spark ML param surface.

Second-algorithm coverage (BASELINE.md config 5). Param names follow Spark's
``org.apache.spark.ml.clustering.KMeans``: k, maxIter, tol, seed, initMode,
featuresCol(=inputCol), predictionCol, weightCol.

Every fit runs on the streamed loop PCA runs: the input — a callable of
chunks, or an array or frame walked as views of its matrix — is a
``data.batches.BatchSource`` in ``batchRows`` batches through
``ops.streaming.IngestTrace`` (put window, staging, landing watcher). The
rows cross once: the first walk puts every batch, keeps it on the chip while
``keep_budget_bytes`` has room and draws the initial centres; each Lloyd
step then walks the kept batches (and puts the rest again) with one
``update_cluster_stats`` a batch and one host read, and a cost pass under
the final centres ends the fit. Row weights (``weightCol``) are the
batches' masks. With ``useXlaDot`` off the same walks run in NumPy float64.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.obs import (
    observed_fit,
    observed_transform,
    transform_phase,
)
from spark_rapids_ml_tpu.data.frame import VectorFrame, as_vector_frame
from spark_rapids_ml_tpu.models.params import (
    HasDeviceId,
    HasInputCol,
    HasWeightCol,
    Param,
)
from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype
from spark_rapids_ml_tpu.utils.timing import PhaseTimer
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

# Host spans of a streamed fit's Lloyd loop, inside ``fit:kmeans`` (opened
# by @observed_fit("kmeans")); the ingest's own (``stream:next``,
# ``stream:put``, ``stream:landing/<id>``) are ``ops.streaming``'s. Not in
# its ``STREAM_SPANS``: the benchmark lists these apart
# (``benchmarks/work/lloyd.py``), and a test holds the two lists equal.
SPAN_INIT = "lloyd:init"  # the first walk: rows put (and kept), centres drawn
SPAN_STEP = "lloyd:step"  # one Lloyd step: a walk over the rows
SPAN_DISPATCH = "lloyd:dispatch"  # one batch's update_cluster_stats call
SPAN_SYNC = "lloyd:sync"  # the step's one host read: how far a centre moved
SPAN_COST = "lloyd:cost"  # the walk under the final centres and its read
LLOYD_SPANS = (SPAN_INIT, SPAN_STEP, SPAN_DISPATCH, SPAN_SYNC, SPAN_COST)
PHASE_INIT = "lloyd/init"
PHASE_DISPATCH = "lloyd/dispatch"
PHASE_SYNC = "lloyd/sync"
PHASE_COST = "lloyd/cost"
INIT_MODES = ("k-means||", "random")


class KMeansParams(HasInputCol, HasDeviceId, HasWeightCol):
    k = Param("k", "number of clusters", 2,
              validator=lambda v: isinstance(v, int) and v >= 1)
    maxIter = Param("maxIter", "maximum Lloyd iterations", 20,
                    validator=lambda v: isinstance(v, int) and v >= 0)
    tol = Param("tol", "center-shift convergence tolerance", 1e-4,
                validator=lambda v: v >= 0)
    seed = Param("seed", "random seed of the initial centres", 0,
                 validator=lambda v: isinstance(v, int))
    initMode = Param(
        "initMode",
        "initial centres: 'random' (k distinct rows drawn uniformly by "
        "seed, Spark's takeSample) or 'k-means||' (Spark's default name; "
        "here k-means++ on the host over a uniform sample of up to "
        "max(4096, 8k) rows — the stand-in for Spark's parallel "
        "k-means||)",
        "k-means||", validator=lambda v: v in INIT_MODES)
    # weightCol (HasWeightCol): weighted Lloyd updates/cost and D^2*w
    # k-means++ sampling — Spark 3.0 weightCol semantics
    predictionCol = Param("predictionCol", "output cluster-id column",
                          "prediction")
    useXlaDot = Param(
        "useXlaDot",
        "run seeding+Lloyd on the accelerator (True) or host NumPy (False)",
        True, validator=lambda v: isinstance(v, bool))
    dtype = Param("dtype", "device compute dtype", "auto",
                  validator=lambda v: v in ("auto", "float32", "float64"))
    batchRows = Param(
        "batchRows",
        "rows per device batch of a streamed fit; 0 = auto-size so one "
        "f32 batch is ~128 MiB",
        0, validator=lambda v: isinstance(v, int) and v >= 0)


class KMeans(KMeansParams):
    """``KMeans().setK(8).fit(df)`` → KMeansModel."""

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_params

        save_params(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "KMeans":
        from spark_rapids_ml_tpu.io.persistence import load_params

        return load_params(KMeans, path)

    @observed_fit("kmeans")
    def fit(self, dataset) -> "KMeansModel":
        """Also accepts an out-of-core source: a zero-arg callable returning
        an iterable of row chunks (re-iterable — Lloyd walks the rows once
        a step, over the batches kept on the chip where it can)."""
        timer = PhaseTimer()
        from spark_rapids_ml_tpu.data.batches import (
            BatchSource,
            streaming_source,
        )

        source = streaming_source(dataset, self.getBatchRows())
        if source is not None:
            self._reject_streamed_weights()
        else:
            frame = as_vector_frame(dataset, self.getInputCol())
            with timer.phase("densify"):
                x = frame.vectors_as_matrix(self.getInputCol())
            source = BatchSource(
                x, batch_rows=self.getBatchRows(),
                weights=self._extract_weights(frame, x.shape[0]))
        if not source.reiterable:
            raise ValueError(
                "KMeans streaming requires a re-iterable source (a "
                "zero-arg callable returning a fresh chunk iterator): "
                "Lloyd makes one pass per iteration"
            )
        rng = np.random.default_rng(self.getSeed())
        init = (_RandomRows if self.getInitMode() == "random"
                else _SampledPlusPlus)(self.getK(), source.n_features, rng)
        fit = (self._streamed_lloyd_xla if self.getUseXlaDot()
               else self._streamed_lloyd_host)
        centers, cost, n_iter, initial = fit(source, init, timer)
        model = KMeansModel(cluster_centers=np.asarray(centers, dtype=np.float64))
        model.uid = self.uid
        model.copy_values_from(self)
        model.training_cost_ = float(cost)
        model.n_iter_ = int(n_iter)
        model.initial_centers_ = np.asarray(initial, dtype=np.float64)
        model.fit_timings_ = timer.as_dict()
        return model

    def _streamed_lloyd_xla(self, source, init, timer):
        """Lloyd on the streamed loop. The first walk (``lloyd:init``) puts
        every batch through ``IngestTrace.put`` and keeps it on the chip
        while the budget has room — its reserve the step's own
        (``ops.kmeans_kernel.step_reserve_bytes``), not a Gram loop's — and
        ``init`` sees each batch, kept or not, to draw the initial centres.
        Each step (``lloyd:step``) walks the kept batches without letting
        them go and puts the rest again (``IngestTrace.step_walk``, pass
        1's deal), one ``update_cluster_stats`` a batch into a donated
        (Σx, count, cost) accumulator, then ``next_centers`` and one host
        read of the largest shift (``lloyd:sync``): the steps stop at
        ``maxIter`` or when every centre moved ≤ ``tol``. The reported cost
        is measured under the FINAL centers, by one more walk
        (``lloyd:cost``), as on the other fit paths. Counters under
        ``fit_report_.extra["lloyd"]``; the ingest's under ``["ingest"]``.
        A weighted source's masks are its rows' weights: the accumulator's
        counts are then weight sums, in ``dtype``."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.obs.report import current_fit
        from spark_rapids_ml_tpu.ops import kmeans_kernel as kk
        from spark_rapids_ml_tpu.ops.streaming import IngestTrace

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        k, n = self.getK(), source.n_features
        # int32 counts where every row weighs 1: exact past 2^24 rows per
        # cluster
        count_dtype = jnp.int32 if source.weights is None else dtype
        ingest = IngestTrace(timer, device)
        counters = {"steps": 0, "batches_from_kept": 0,
                    "batches_put_again": 0, "sync_seconds": 0.0}
        current_fit().note(lloyd=counters)  # filled as the fit goes

        def walk_stats(centers):
            """(Σx, count, cost) of every row under ``centers``: one walk."""
            carry = (jnp.zeros((k, n), dtype=dtype, device=device),
                     jnp.zeros((k,), dtype=count_dtype, device=device),
                     jnp.zeros((), dtype=dtype, device=device))
            for _, x_dev, m_dev in ingest.step_walk(source, dtype, counters):
                with ingest.stage(SPAN_DISPATCH, PHASE_DISPATCH):
                    carry = kk.update_cluster_stats(carry, centers, x_dev,
                                                    m_dev)
            return carry

        reserve = kk.step_reserve_bytes(source.batch_rows, k, n,
                                        jnp.dtype(dtype).itemsize)
        with ingest.stepping(reserve, n), timer.phase("fit_kernel"):
            with timer.phase(PHASE_INIT), ingest.walk(SPAN_INIT):
                for batch, mask in ingest.batches(source):
                    c, x_dev, m_dev = ingest.put(batch, mask, dtype)
                    kept = ingest.keep(c, x_dev, m_dev)
                    init.see(batch, mask, len(ingest.kept) - 1 if kept else -1)
                centers = init.device_centers(ingest, dtype, device)
            initial = centers
            for _ in range(self.getMaxIter()):
                with ingest.walk(SPAN_STEP):
                    centers, shift = kk.next_centers(walk_stats(centers),
                                                     centers)
                    with ingest.stage(SPAN_SYNC, PHASE_SYNC):
                        moved = float(shift)
                counters["steps"] += 1
                counters["sync_seconds"] = timer.as_dict()[PHASE_SYNC]
                if moved <= self.getTol():
                    break
            with timer.phase(PHASE_COST), ingest.walk(SPAN_COST):
                cost = float(walk_stats(centers)[2])
        return (np.asarray(centers), cost, counters["steps"],
                np.asarray(initial))

    def _streamed_lloyd_host(self, source, init, timer):
        from spark_rapids_ml_tpu.data.batches import weighted_rows

        with timer.phase("seed"), TraceRange("kmeans seed", TraceColor.ORANGE):
            for batch, mask in source.batches():
                init.see(batch, mask)
            centers = init.host_centers()
        initial = centers
        k, n = centers.shape

        def pass_stats(c):
            sums = np.zeros((k, n))
            counts = np.zeros(k)
            cost = 0.0
            for batch, mask in source.batches():
                b, w = weighted_rows(batch, mask)
                d = _sqdist(b, c)
                labels = d.argmin(axis=1)
                np.add.at(sums, labels, b * w[:, None])
                np.add.at(counts, labels, w)
                cost += float((d.min(axis=1) * w).sum())
            return sums, counts, cost

        n_iter = 0
        with timer.phase("fit_kernel"), TraceRange("kmeans host", TraceColor.ORANGE):
            for n_iter in range(1, self.getMaxIter() + 1):
                sums, counts, _ = pass_stats(centers)
                # by the cluster's whole weight, however small
                new_centers = np.where(
                    counts[:, None] > 0,
                    sums / np.where(counts > 0, counts, 1.0)[:, None],
                    centers,
                )
                moved = float(np.sqrt(
                    ((new_centers - centers) ** 2).sum(axis=1).max()
                ))
                centers = new_centers
                if moved <= self.getTol():
                    break
            _, _, cost = pass_stats(centers)
        return centers, cost, n_iter, initial


def _sqdist(x, centers):
    x2 = (x * x).sum(axis=1)[:, None]
    c2 = (centers * centers).sum(axis=1)[None, :]
    return np.maximum(x2 + c2 - 2.0 * (x @ centers.T), 0.0)


class _RandomRows:
    """``initMode`` "random", the one draw of every fit: k distinct rows
    with a weight drawn uniformly by ``seed`` while the rows are walked
    once — Algorithm R over the row indices, one draw a row, a batch at a
    time — so no walk is made for it. A row of a batch the fit keeps on
    the chip is remembered by where it is and gathered there
    (``ops.kmeans_kernel.take_rows``); any other is copied from the host
    batch while the walk has it."""

    def __init__(self, k: int, n_features: int, rng):
        self.k, self.rng, self.seen = k, rng, 0
        self.host = np.zeros((k, n_features))
        self.kept = np.full(k, -1)  # the kept batch a slot's row is in
        self.row = np.zeros(k, dtype=np.int32)  # its row there

    def see(self, batch, mask, kept: int = -1) -> None:
        """The walk's next batch; ``kept``: its index among the batches the
        fit keeps, -1 where it keeps it not. Its rows are those the mask
        gives a weight: all, where it has none."""
        valid = np.arange(batch.shape[0]) if mask is None \
            else np.flatnonzero(mask > 0)
        t = self.seen + np.arange(valid.size)
        slot = np.where(t < self.k, t, self.rng.integers(0, t + 1))
        hit = np.flatnonzero(slot < self.k)
        # a later row of the batch takes a slot over from an earlier one
        slots, last = np.unique(slot[hit][::-1], return_index=True)
        rows = valid[hit[::-1][last]]
        if kept < 0:
            self.host[slots] = batch[rows]
        self.kept[slots] = kept
        self.row[slots] = rows
        self.seen += valid.size

    def _check(self) -> None:
        if self.k > self.seen:
            raise ValueError(
                f"k = {self.k} must be at most the number of rows {self.seen}")

    def host_centers(self) -> np.ndarray:
        self._check()
        return self.host

    def device_centers(self, ingest, dtype, device):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import kmeans_kernel as kk

        self._check()
        centers = jax.device_put(np.asarray(self.host, dtype=dtype), device)
        rows = jax.device_put(self.row, device)
        for b in np.unique(self.kept[self.kept >= 0]):
            centers = kk.take_rows(centers, ingest.kept[b][3], rows,
                                   jnp.asarray(self.kept == b, device=device))
        return centers


class _SampledPlusPlus:
    """``initMode`` "k-means||" — the stand-in for Spark's parallel
    k-means||: a uniform sample of up to max(4096, 8k) rows drawn while the
    rows are walked once, then k-means++ over it on the host in float64
    (``_host_kmeans_pp``), each sampled row with its weight.

    The sample is a vectorized batch reservoir: row t (0-based global
    index) replaces a random slot with probability size/(t+1) — per-batch
    vectorization of Algorithm R, accepted approximation for seeding
    purposes."""

    def __init__(self, k: int, n_features: int, rng):
        self.k, self.rng = k, rng
        self.size = max(4096, 8 * k)
        self.reservoir = np.empty((self.size, n_features), dtype=np.float64)
        self.weights = np.ones(self.size)
        self.weighted = False
        self.filled = self.seen = 0

    def see(self, batch, mask, kept: int = -1) -> None:
        """The walk's next batch: its rows the mask gives a weight."""
        rows, weights = batch, np.ones(batch.shape[0])
        if mask is not None:
            counts = mask > 0
            rows, weights = batch[counts], np.asarray(mask[counts], float)
            self.weighted |= mask.dtype != bool
        take = min(self.size - self.filled, rows.shape[0])
        if take > 0:
            self.reservoir[self.filled:self.filled + take] = rows[:take]
            self.weights[self.filled:self.filled + take] = weights[:take]
            self.filled += take
            self.seen += take
            rows, weights = rows[take:], weights[take:]
        if rows.shape[0] == 0:
            return
        t = self.seen + np.arange(rows.shape[0])
        keep = self.rng.random(rows.shape[0]) < self.size / (t + 1)
        idx = np.nonzero(keep)[0]
        if idx.size:
            slots = self.rng.integers(0, self.size, size=idx.size)
            self.reservoir[slots] = rows[idx]
            self.weights[slots] = weights[idx]
        self.seen += rows.shape[0]

    def host_centers(self) -> np.ndarray:
        sample = self.reservoir[:self.filled]
        if self.k > sample.shape[0]:
            raise ValueError(
                f"k = {self.k} must be at most the number of rows "
                f"{sample.shape[0]}"
            )
        return _host_kmeans_pp(
            sample, self.k, self.rng,
            weights=self.weights[:self.filled] if self.weighted else None)

    def device_centers(self, ingest, dtype, device):
        import jax

        return jax.device_put(np.asarray(self.host_centers(), dtype=dtype),
                              device)


def _host_kmeans_pp(x, k, rng, weights=None):
    centers = np.empty((k, x.shape[1]), dtype=np.float64)
    if weights is None:
        centers[0] = x[rng.integers(len(x))]
    else:
        pw = weights / weights.sum()
        centers[0] = x[rng.choice(len(x), p=pw)]
    w = np.ones(len(x)) if weights is None else weights
    min_d = ((x - centers[0]) ** 2).sum(axis=1) * w
    for i in range(1, k):
        p = min_d / min_d.sum() if min_d.sum() > 0 else (
            w / w.sum() if weights is not None else None
        )
        centers[i] = x[rng.choice(len(x), p=p)]
        min_d = np.minimum(min_d, ((x - centers[i]) ** 2).sum(axis=1) * w)
    return centers


class KMeansModel(KMeansParams):
    def __init__(self, cluster_centers: Optional[np.ndarray] = None,
                 uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.cluster_centers = cluster_centers
        self.training_cost_ = None
        self.n_iter_ = None
        self.initial_centers_ = None
        self.fit_timings_ = {}

    def _copy_internal_state(self, other: "KMeansModel") -> None:
        other.cluster_centers = self.cluster_centers
        other.training_cost_ = self.training_cost_
        other.n_iter_ = self.n_iter_
        other.initial_centers_ = self.initial_centers_

    # Spark API naming
    def clusterCenters(self):
        return [c for c in self.cluster_centers]

    @observed_transform("kmeans")
    def transform(self, dataset) -> VectorFrame:
        if self.cluster_centers is None:
            raise ValueError("model has no centers; fit first or load")
        frame = as_vector_frame(dataset, self.getInputCol())
        x = frame.vectors_as_matrix(self.getInputCol())
        if self.getUseXlaDot():
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops.kmeans_kernel import (
                assign_clusters_jit,
            )
            from spark_rapids_ml_tpu.utils.padding import (
                pad_to_bucket,
                transform_padding_enabled,
            )

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            # Bucket-pad ragged batches so varying-size callers share a few
            # compiled assign signatures; pad-row labels are sliced off.
            n_rows = x.shape[0]
            if transform_padding_enabled():
                x, n_rows = pad_to_bucket(x)
            with transform_phase("device_put"):
                x_dev = jax.device_put(jnp.asarray(x, dtype=dtype), device)
                c_dev = jax.device_put(
                    jnp.asarray(self.cluster_centers, dtype=dtype), device
                )
            with transform_phase("compute"):
                labels_dev = assign_clusters_jit(x_dev, c_dev)
            with transform_phase("host_sync"):
                labels = np.asarray(
                    jax.block_until_ready(labels_dev))[:n_rows]
        else:
            with transform_phase("compute"):
                labels = _sqdist(x, self.cluster_centers).argmin(axis=1)
        return frame.with_column(
            self.getPredictionCol(), labels.astype(np.int32).tolist()
        )

    def _serving_weights(self, precision: str, device, dtype):
        """Device-staged centers for one precision — shared by the
        standalone serving program and the fused-pipeline stage hook."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric_host

        if precision == "bf16":
            return (jax.device_put(jnp.asarray(
                self.cluster_centers, dtype=jnp.bfloat16), device),)
        if precision == "int8":
            q, scale = quantize_symmetric_host(self.cluster_centers)
            return (jax.device_put(jnp.asarray(q), device), scale)
        return (jax.device_put(jnp.asarray(
            self.cluster_centers, dtype=dtype), device),)

    def serving_stage(self, precision: str = "native", *,
                      device=None, dtype=None):
        """Composable fused-pipeline stage: the un-jitted assignment
        body + staged centers. TERMINAL — labels are output-typed and
        cannot feed a downstream transformer."""
        if self.cluster_centers is None or not self.getUseXlaDot():
            return None
        from spark_rapids_ml_tpu.models._serving import (
            ServingStage,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import kmeans_kernel as _kk

        if device is None or dtype is None:
            device, dtype, _ = resolve_serving_context(self)
        body = _kk.SERVING_STAGE_BODIES.get(precision)
        if body is None:
            raise ValueError(f"unknown serving precision {precision!r}")
        return ServingStage(
            fn=body,
            weights=self._serving_weights(precision, device, dtype),
            algo="kmeans",
            terminal=True,
            fetch_dtype=np.dtype(np.int32),
        )

    def serving_transform_program(self, precision: str = "native",
                                  device=None):
        """Device-resident serving program for the pipelined batcher
        (``obs.serving.ServingProgram``): centers staged once, ``run``
        async-dispatches the assignment kernel (distance argmin — the
        int8/bf16 variants reduce only the cross-term GEMM), ``fetch``
        the completion-step sync. ``device`` pins one replica's device
        (the multi-device tier builds one program per chip). None for
        host-path models."""
        if self.cluster_centers is None or not self.getUseXlaDot():
            return None
        from spark_rapids_ml_tpu.models._serving import (
            build_serving_program,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import kmeans_kernel as _kk

        device, dtype, donate = resolve_serving_context(self, device=device)
        weights = self._serving_weights(precision, device, dtype)
        return build_serving_program(
            device=device, dtype=dtype, algo="kmeans",
            precision=precision,
            kernels={
                "native": (_kk.assign_clusters_serve if donate
                           else _kk.assign_clusters_jit),
                "bf16": _kk.assign_clusters_bf16,
                "int8": _kk.assign_clusters_int8,
            },
            weights=weights,
            # int32 labels, matching the sync path's prediction column
            fetch_dtype=np.int32,
        )

    def compute_cost(self, dataset) -> float:
        """Sum of squared distances to nearest center (Spark computeCost)."""
        frame = as_vector_frame(dataset, self.getInputCol())
        x = frame.vectors_as_matrix(self.getInputCol())
        return float(_sqdist(x, self.cluster_centers).min(axis=1).sum())

    computeCost = compute_cost

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_kmeans_model

        save_kmeans_model(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "KMeansModel":
        from spark_rapids_ml_tpu.io.persistence import load_kmeans_model

        return load_kmeans_model(path)
