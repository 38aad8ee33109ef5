"""PCA Estimator / Model — the user-facing drop-in API.

Parity target: ``com.nvidia.spark.ml.feature.PCA`` →
``org.apache.spark.ml.feature.RapidsPCA[Model]``
(``/root/reference/src/main/scala/org/apache/spark/ml/feature/RapidsPCA.scala``).
Same Estimator/Model/Params shape, same fit pipeline (select input column →
require k ≤ numFeatures → covariance → eigensolve → model,
``RapidsPCA.scala:111-125``), same transform semantics (project WITHOUT mean
subtraction, ``RapidsPCA.scala:187-189``), same persistence layout
(metadata JSON + Parquet payload, ``RapidsPCA.scala:218-254``).

``fit`` has one body for every input. A callable or an iterator of chunks
is a stream as it comes; an array, a frame or a list of vectors is made a
matrix and walked as views of it (``data.batches.BatchSource``: one batch
of exactly its rows when it is under ``batchRows``). The stream goes through
``ops.streaming.stream_covariance`` on the chips of ``numDevices`` and the
covariance through ``ops.eigh.pca_from_covariance_gated``; ``useXlaDot`` and
``useXlaSvd`` move either stage to the host, nothing else forks.

TPU-first differences (all documented in SURVEY.md §3.6/§7):
* ``useGemm``/``useCuSolverSVD`` become ``useXlaDot``/``useXlaSvd``: True
  runs that stage's jit-compiled programs on the selected accelerator; False
  runs it on the host in float64 (NumPy, and native C++ ``libtpuml`` LAPACK
  for a small eigensolve when built) — mirroring the reference's GPU/CPU
  path toggles but never requiring the native library for CPU-only runs
  (fixes the §3.4 coupling).
* batched on-device transform is ENABLED (the reference left it commented
  out pending perf work, ``RapidsPCA.scala:172-190``).
* covariance normalizes by numRows−1 on every path and ``meanCentering=False``
  works on every path (reference bugs, §3.6).
* explained variance is λ/Σλ on every path (the reference GPU path's √λ
  inconsistency is not replicated).
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
    HasOutputCol,
    Param,
    Params,
)
from spark_rapids_ml_tpu.utils.numeric import (
    GRAM_PRECISIONS as _GRAM_PRECISIONS,
)
from spark_rapids_ml_tpu.utils.timing import PhaseTimer
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

# Host spans of the fit, outermost first; the stages inside
# SPAN_STREAMED_COV are ops.streaming.STREAM_SPANS. The benchmark reads a
# trace by these names (benchmarks/work/spans.py).
SPAN_FIT = "fit:pca"  # opened by @observed_fit("pca")
SPAN_STREAMED_COV = "streamed cov"
SPAN_XLA_EIGH = "xla eigh"
# what follows the solve (``fit_timings_["fetch"]``): pc, explained variance
# and mean to the host, float64 copies, the PCAModel
SPAN_FETCH = "fit:fetch"


class PCAParams(HasInputCol, HasOutputCol, HasDeviceId):
    """Shared params, mirroring ``RapidsPCAParams`` (``RapidsPCA.scala:30-75``)."""

    k = Param(
        "k",
        "number of principal components",
        None,
        validator=lambda v: isinstance(v, int) and v >= 1,
    )
    outputCol = Param("outputCol", "output column name", "pca_features")
    meanCentering = Param(
        "meanCentering",
        "whether to center data before computing covariance",
        True,
        validator=lambda v: isinstance(v, bool),
    )
    useXlaDot = Param(
        "useXlaDot",
        "covariance accumulated on the accelerator, batch by batch (True), "
        "or on the host in float64 (False); analogue of the reference's "
        "useGemm",
        True,
        validator=lambda v: isinstance(v, bool),
    )
    useXlaSvd = Param(
        "useXlaSvd",
        "eigensolve via XLA on the accelerator (True) or host fallback "
        "(False); analogue of the reference's useCuSolverSVD",
        True,
        validator=lambda v: isinstance(v, bool),
    )
    dtype = Param(
        "dtype",
        "device compute dtype: 'float32', 'float64', or 'auto' (float64 when "
        "jax x64 is enabled, else float32); parity tests run float64, TPU "
        "production runs float32 with HIGHEST-precision matmuls",
        "auto",
        validator=lambda v: v in ("auto", "float32", "float64"),
    )
    svdSolver = Param(
        "svdSolver",
        "eigensolver for the XLA path: 'eigh' (dense full-spectrum, exact "
        "per-vector parity with the LAPACK/Spark oracle) or 'randomized' "
        "(top-k Halko-Martinsson-Tropp subspace iteration, O(n^2 k) MXU "
        "matmuls instead of O(n^3) — ~100x faster at n=4096 k=256, "
        "per-vector accuracy depends on spectral gaps; see "
        "ops/randomized.py) or 'auto' (randomized when k<<n on large "
        "covariances, residual-gated with dense-eigh fallback — see "
        "ops.eigh.pca_from_covariance_gated; the model records the choice "
        "in svd_solver_used_). The host solve (useXlaSvd=False) always "
        "uses dense LAPACK regardless.",
        "auto",
        validator=lambda v: v in ("auto", "eigh", "randomized"),
    )
    batchRows = Param(
        "batchRows",
        "rows per device batch of the fit's stream, whatever form the rows "
        "come in; 0 = auto-size so one f32 batch is ~128 MiB (a matrix "
        "under that is one batch of exactly its rows)",
        0,
        validator=lambda v: isinstance(v, int) and v >= 0,
    )
    gramPrecision = Param(
        "gramPrecision",
        "MXU precision for the Gram/covariance matmul — the documented "
        "accuracy/speed trade (the analogue of the reference's "
        "useGemm/useCuSolverSVD toggles, RapidsPCA.scala:30-75). "
        "'auto' (default) defers to TPUML_GRAM_PRECISION (bfloat16_3x: "
        "3-pass bf16 split with f32 accumulation — measured numerically "
        "indistinguishable from 'highest' on the covariance oracle, "
        "~1.3x faster). 'bfloat16' opts into the single-pass bf16 arm, "
        "with a RELAXED accuracy contract: covariance error "
        "grows with conditioning, so use it when the spectrum is "
        "well-separated and ~1e-2 relative component error is "
        "acceptable. 'float32'/'highest' force full-precision passes.",
        "auto",
        validator=lambda v: v == "auto" or v in _GRAM_PRECISIONS,
    )
    numDevices = Param(
        "numDevices",
        "how many of the process's local chips a fit may use: the "
        "chip deviceId resolves to and the next numDevices-1 local ones "
        "(the in-process form of spark.executor.resource.tpu.amount). "
        "Host batches are dealt to the chips whole and in turn, each chip "
        "accumulates its own with the one-chip programs, and two "
        "all-reduces join them (ops.streaming.stream_covariance). "
        "1 (default) = the one chip deviceId names, whatever the host has.",
        1,
        validator=lambda v: isinstance(v, int) and v >= 1,
    )


def _resolve_dtype(dtype_param: str):
    import jax
    import jax.numpy as jnp

    if dtype_param == "float64":
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "dtype='float64' requires jax x64 mode "
                "(jax.config.update('jax_enable_x64', True)); refusing to "
                "silently downcast to float32"
            )
        return jnp.float64
    if dtype_param == "float32":
        return jnp.float32
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _resolve_device(device_id: int):
    """deviceId −1 ⇒ task-assigned resource / env / default 0, else the
    explicit ordinal — the reference's gpuId discovery semantics
    (``RapidsRowMatrix.scala:171-175``), with the TaskContext role played by
    ``utils.resources.resolve_device_ordinal``."""
    import jax

    from spark_rapids_ml_tpu.utils.resources import resolve_device_ordinal

    devices = jax.local_devices()
    ordinal = resolve_device_ordinal(device_id)
    # Addresses name chips, not list positions: match by device.id first
    # (JAX's stable chip id, correct on multi-host where jax.devices() spans
    # hosts), then positionally; a pinned executor (TPU_VISIBLE_CHIPS="2")
    # re-enumerates its single visible device, so the assigned address maps
    # to the only device present.
    for d in devices:
        if d.id == ordinal:
            return d
    if 0 <= ordinal < len(devices):
        return devices[ordinal]
    if len(devices) == 1:
        # Last resort: run on the only visible device even though its id
        # doesn't match the assignment. With pinning env present this is the
        # normal pinned-executor shape (TPU_VISIBLE_CHIPS="2" re-enumerates
        # the sole visible chip as id 0) — silent. Without pinning env the
        # assignment has nothing backing it (env lost or mis-set): warn so a
        # misrouted task is diagnosable instead of silently computing on the
        # wrong chip.
        import os
        import warnings

        from spark_rapids_ml_tpu.utils.resources import _ENV_VISIBLE

        if not any(os.environ.get(v) for v in _ENV_VISIBLE):
            warnings.warn(
                f"deviceId {ordinal} does not match the single visible "
                f"device (id {devices[0].id}) and no chip-pinning env "
                f"({'/'.join(_ENV_VISIBLE)}) is set; running on the visible "
                f"device anyway. Check task resource assignment.",
                RuntimeWarning,
                stacklevel=2,
            )
        return devices[0]
    raise ValueError(
        f"deviceId {ordinal} matches none of the {len(devices)} visible "
        f"local devices (ids {[d.id for d in devices]})"
    )


def _resolve_devices(device_id: int, num_devices: int):
    """The chips of a fit: ``_resolve_device(device_id)`` and the
    ``num_devices - 1`` local chips after it."""
    first = _resolve_device(device_id)
    if num_devices == 1:
        return (first,)
    import jax

    local = jax.local_devices()
    start = local.index(first)
    if start + num_devices > len(local):
        raise ValueError(
            f"numDevices {num_devices} from local chip {start} on: the "
            f"process has {len(local)} local devices")
    return tuple(local[start:start + num_devices])


class PCA(PCAParams):
    """Estimator. ``PCA().setK(3).setInputCol('features').fit(df)``."""

    def save(self, path: str, overwrite: bool = False) -> None:
        """Params-only persistence, as ``DefaultParamsWritable``
        (``PCA.scala:27-37`` companion object)."""
        from spark_rapids_ml_tpu.io.persistence import save_params

        save_params(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "PCA":
        from spark_rapids_ml_tpu.io.persistence import load_params

        return load_params(PCA, path)

    @observed_fit("pca")
    def fit(self, dataset) -> "PCAModel":
        timer = PhaseTimer()
        self._svd_solver_used = None  # set by device solves; None = host LAPACK
        k = self.getK()
        if k is None:
            raise ValueError("k must be set before fit()")

        from spark_rapids_ml_tpu.data.batches import BatchSource, streaming_source

        # Every input is a stream of fixed-shape batches: a callable or an
        # iterator of chunks as it comes, anything else densified and walked
        # as views of the matrix (one batch of exactly its rows when it is
        # under batchRows) — the analogue of the reference's per-partition
        # chunking (RapidsRowMatrix.scala:168-202). A columnar chunk (an
        # Arrow record batch) gives its vector column: inputCol where that
        # is set, else its only one.
        source = streaming_source(
            dataset, self.getBatchRows(),
            self.getInputCol() if self.isSet("inputCol") else None)
        if source is None:
            frame = as_vector_frame(dataset, self.getInputCol())
            with timer.phase("densify"):
                x_host = frame.vectors_as_matrix(self.getInputCol())
            source = BatchSource(x_host, batch_rows=self.getBatchRows())
        if k > source.n_features:
            raise ValueError(
                f"k = {k} must be at most the number of features "
                f"{source.n_features}"
            )

        cov, mean, count, ingest = self._covariance(source, timer)
        if self.getMeanCentering() and float(count) < 2:
            # matches `require(count > 1)` (RapidsRowMatrix.scala:160)
            raise ValueError("mean centering requires more than one row")
        pc, evr = self._solve(cov, k, timer, ingest)

        # device results cross to the host here (a device stage hands its
        # arrays back where they are)
        with timer.phase("fetch"), TraceRange(SPAN_FETCH, TraceColor.CYAN):
            model = PCAModel(
                pc=np.asarray(pc, dtype=np.float64),
                explained_variance=np.asarray(evr, dtype=np.float64),
                mean=np.asarray(mean, dtype=np.float64),
            )
            model.uid = self.uid
            model.copy_values_from(self)
        model.fit_timings_ = timer.as_dict()
        model.svd_solver_used_ = getattr(self, "_svd_solver_used", None)
        return model

    def _gram_precision(self):
        """The resolved ``gramPrecision`` param: None when 'auto' (each
        kernel then defers to TPUML_GRAM_PRECISION at trace time), else
        the validated explicit value — which wins over the env var and
        participates in every jit cache key it reaches."""
        value = self.get_or_default("gramPrecision")
        if value == "auto":
            return None
        from spark_rapids_ml_tpu.ops.covariance import resolve_gram_precision

        return resolve_gram_precision(value)

    def _covariance(self, source, timer):
        """(covariance, mean, row count, the stream's ``IngestTrace``): on
        the fit's chips (``useXlaDot``; device arrays on the first of them)
        or, with no trace, on the host in float64."""
        if not self.getUseXlaDot():
            with timer.phase("covariance"), TraceRange(
                "host cov", TraceColor.ORANGE
            ):
                return *_host_covariance_streamed(
                    source, self.getMeanCentering()), None
        import jax

        from spark_rapids_ml_tpu.ops.streaming import (
            SPAN_SYNC_COV,
            IngestTrace,
            stream_covariance,
        )

        ingest = IngestTrace(timer, _resolve_devices(
            self.getDeviceId(), self.getNumDevices()))
        with timer.phase("covariance"), TraceRange(
            SPAN_STREAMED_COV, TraceColor.RED
        ):
            cov, mean, count = stream_covariance(
                source,
                mean_centering=self.getMeanCentering(),
                dtype=_resolve_dtype(self.getDtype()),
                precision=self._gram_precision(),
                ingest=ingest,
            )
            with ingest.sync(SPAN_SYNC_COV):
                cov = jax.block_until_ready(cov)
            ingest.all_landed()  # every batch put is in ``cov``
        return cov, mean, count, ingest

    def _solve(self, cov, k, timer, ingest):
        """Top-k (components, explained variance) of ``cov``: on the chip
        (``useXlaSvd``; a host covariance, which has no ``ingest``, is put
        there first) or on the host in float64."""
        if not self.getUseXlaSvd():
            with timer.phase("solve"), TraceRange("host eigh", TraceColor.BLUE):
                return _host_eig_topk(np.asarray(cov, dtype=np.float64), k)
        if ingest is not None:
            ingest.hbm("solve:start")
        pc, evr, self._svd_solver_used = solve_on_chip(
            cov, k, self.getSvdSolver(), timer,
            self.getDeviceId(), self.getDtype())
        if ingest is not None:
            ingest.hbm("solve:end")
        return pc, evr


def solve_on_chip(cov, k: int, solver: str, timer: PhaseTimer,
                  device_id: int = -1, dtype: str = "auto"):
    """(components, explained variance, the solver that answered): the top
    k of ``cov`` through the residual gate (``ops.eigh
    .pca_from_covariance_gated``: 'auto' → randomized when k ≪ n, verified,
    dense-eigh fallback; it notes ``solve`` on the fit's report), as the
    phase ``solve`` under the span ``xla eigh``. A covariance that is not on
    a chip yet — a host stage's, or the Spark front's merged one — is put
    on the chip ``device_id`` names as ``dtype`` first, inside the phase.
    The one device solve of a PCA fit: ``PCA.fit`` and the Spark front's
    driver both end here."""
    import jax

    from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance_gated

    with timer.phase("solve"), TraceRange(SPAN_XLA_EIGH, TraceColor.BLUE):
        if not isinstance(cov, jax.Array):
            cov = jax.device_put(np.asarray(cov, dtype=_resolve_dtype(dtype)),
                                 _resolve_device(device_id))
        pc, evr, used = pca_from_covariance_gated(cov, k, solver=solver)
        return (*jax.block_until_ready((pc, evr)), used)


def _host_covariance_streamed(source, mean_centering: bool):
    """Out-of-core host covariance: float64 accumulation per bucket.

    Two-pass (mean, then centered Gram) for re-iterable sources — the same
    schedule the device path uses; one-pass sufficient statistics otherwise.
    """
    n = source.n_features
    if mean_centering and source.reiterable:
        col_sum = np.zeros(n)
        count = 0
        for batch, mask in source.batches():
            b = batch if mask is None else batch[mask]
            col_sum += b.sum(axis=0)
            count += b.shape[0]
        mean = col_sum / max(count, 1)
        g = np.zeros((n, n))
        for batch, mask in source.batches():
            b = batch if mask is None else batch[mask]
            bc = np.asarray(b, dtype=np.float64) - mean
            g += bc.T @ bc
        return g / max(count - 1, 1), mean, count

    g = np.zeros((n, n))
    col_sum = np.zeros(n)
    count = 0
    for batch, mask in source.batches():
        b = batch if mask is None else batch[mask]
        b = np.asarray(b, dtype=np.float64)
        g += b.T @ b
        col_sum += b.sum(axis=0)
        count += b.shape[0]
    denom = max(count - 1, 1)
    if not mean_centering:
        return g / denom, np.zeros(n), count
    mean = col_sum / max(count, 1)
    cov = (g - count * np.outer(mean, mean)) / denom
    return cov, mean, count


# Above this n the host eigensolve routes to NumPy's threaded OpenBLAS:
# the native entry dlopens the SYSTEM LAPACK (netlib), measured ~9× slower
# at n=4096 (95s vs 10.7s) though numerically identical. Below it the
# native path is sub-second and keeps the parity surface exercised.
_NATIVE_EIGH_MAX_N = 1024


def _host_eig_topk(cov: np.ndarray, k: int):
    """Host eigensolve + shared postprocessing (descending order, sign-flip,
    λ/Σλ). Native C++ (LAPACK dsyevd via dlopen, Jacobi fallback) for small
    n when built; NumPy/OpenBLAS otherwise or for large n."""
    from spark_rapids_ml_tpu import native
    from spark_rapids_ml_tpu.ops.eigh import pca_postprocess_host

    if native.is_loaded() and cov.shape[0] <= _NATIVE_EIGH_MAX_N:
        evals, evecs = native.syevd(np.ascontiguousarray(cov, dtype=np.float64))
    else:
        evals, evecs = np.linalg.eigh(cov)
    return pca_postprocess_host(evals, evecs, k)


class PCAModel(PCAParams):
    """Fitted transformer holding ``pc`` (n_features × k) and
    ``explained_variance`` (k,), as ``RapidsPCAModel`` does
    (``RapidsPCA.scala:146-210``)."""

    def __init__(
        self,
        pc: Optional[np.ndarray] = None,
        explained_variance: Optional[np.ndarray] = None,
        mean: Optional[np.ndarray] = None,
        uid: Optional[str] = None,
    ):
        super().__init__(uid=uid)
        self.pc = pc
        self.explained_variance = explained_variance
        self.mean = mean
        self.fit_timings_ = {}
        self.svd_solver_used_ = None

    def _copy_internal_state(self, other: "PCAModel") -> None:
        other.pc = self.pc
        other.explained_variance = self.explained_variance
        other.mean = self.mean
        other.svd_solver_used_ = self.svd_solver_used_

    @property
    def explainedVariance(self):
        return self.explained_variance

    @observed_transform("pca")
    def transform(self, dataset) -> VectorFrame:
        """Batched on-device projection — one MXU matmul over the whole
        batch (the path the reference disabled, ``RapidsPCA.scala:172-190``).
        Falls back to host GEMM when ``useXlaDot=False``."""
        if self.pc is None:
            raise ValueError("model has no components; fit first or load")
        frame = as_vector_frame(dataset, self.getInputCol())
        self.transform_schema(frame.columns)
        x_host = frame.vectors_as_matrix(self.getInputCol())
        if x_host.shape[1] != self.pc.shape[0]:
            raise ValueError(
                f"input has {x_host.shape[1]} features, model expects "
                f"{self.pc.shape[0]}"
            )
        if self.getUseXlaDot():
            import jax

            from spark_rapids_ml_tpu.ops.pca_kernel import pca_transform_kernel
            from spark_rapids_ml_tpu.utils.padding import (
                pad_to_bucket,
                transform_padding_enabled,
            )

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            # Pad ragged batch sizes up to a shape bucket so varying-size
            # callers reuse a handful of compiled signatures (projection is
            # row-independent — real rows are bit-identical; pad rows are
            # sliced off before anyone sees them).
            n_rows = x_host.shape[0]
            if transform_padding_enabled():
                x_host, n_rows = pad_to_bucket(x_host)
            with TraceRange("xla transform", TraceColor.GREEN):
                with transform_phase("device_put"):
                    x = jax.device_put(
                        np.asarray(x_host, dtype=dtype), device)
                    pc = jax.device_put(
                        np.asarray(self.pc, dtype=dtype), device)
                with transform_phase("compute"):
                    out_dev = pca_transform_kernel(x, pc)
                with transform_phase("host_sync"):
                    out = np.asarray(jax.block_until_ready(out_dev))[:n_rows]
        else:
            from spark_rapids_ml_tpu import native

            with TraceRange("host transform", TraceColor.GREEN):
                with transform_phase("compute"):
                    if native.is_loaded():
                        out = native.gemm(
                            np.ascontiguousarray(x_host),
                            np.ascontiguousarray(self.pc, dtype=np.float64),
                        )
                    else:
                        out = x_host @ self.pc
        return frame.with_column(self.getOutputCol(), np.asarray(out, dtype=np.float64))

    def _serving_weights(self, precision: str, device, dtype):
        """Device-staged constant operands (the components) for one
        precision — staged ONCE per program, shared by the standalone
        serving program and the fused-pipeline stage hook."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric_host

        if precision == "bf16":
            return (jax.device_put(
                np.asarray(self.pc, dtype=jnp.bfloat16), device),)
        if precision == "int8":
            q, scale = quantize_symmetric_host(self.pc)
            return (jax.device_put(q, device), scale)
        return (jax.device_put(
            np.asarray(self.pc, dtype=dtype), device),)

    def serving_stage(self, precision: str = "native", *,
                      device=None, dtype=None):
        """The composable fused-pipeline stage (``models._serving
        .ServingStage``): the un-jitted projection body + device-staged
        components, for ``PipelineModel.serving_transform_program`` to
        compose into ONE XLA program with its neighbours. Projection is
        float → float, so PCA may sit anywhere in a fused chain."""
        if self.pc is None or not self.getUseXlaDot():
            return None
        from spark_rapids_ml_tpu.models._serving import (
            ServingStage,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import pca_kernel as _pk

        if device is None or dtype is None:
            device, dtype, _ = resolve_serving_context(self)
        body = _pk.SERVING_STAGE_BODIES.get(precision)
        if body is None:
            raise ValueError(f"unknown serving precision {precision!r}")
        return ServingStage(
            fn=body,
            weights=self._serving_weights(precision, device, dtype),
            algo="pca",
            fetch_dtype=np.dtype(np.float64),
        )

    def serving_transform_program(self, precision: str = "native",
                                  device=None):
        """The device-resident serving program for the pipelined
        micro-batcher (``obs.serving.ServingProgram``): components staged
        to the device ONCE, ``put`` starting each batch's host→device
        transfer, ``run`` async-dispatching the projection kernel
        (donated staged input off-CPU), ``fetch`` the completion-step
        host sync. ``precision`` selects the env-gated reduced-precision
        variant ladder (bf16 / int8 GEMM — separate tracked signatures
        per bucket, guarded by the engine's offline max-error check and
        the numerics sentinel); ``device`` pins the program onto one
        replica's device (``serve/placement.py`` builds one program per
        visible device; None = the model's own device resolution).
        Returns None for host-path models (``useXlaDot=False``) — the
        engine then keeps the blocking sync path."""
        if self.pc is None or not self.getUseXlaDot():
            return None
        from spark_rapids_ml_tpu.models._serving import (
            build_serving_program,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import pca_kernel as _pk

        device, dtype, donate = resolve_serving_context(self, device=device)
        weights = self._serving_weights(precision, device, dtype)
        return build_serving_program(
            device=device, dtype=dtype, algo="pca", precision=precision,
            kernels={
                "native": (_pk.pca_transform_serve if donate
                           else _pk.pca_transform_kernel),
                "bf16": _pk.pca_transform_bf16,
                "int8": _pk.pca_transform_int8,
            },
            weights=weights,
            # f64 to match the sync path's output column exactly
            # (bit-equal at native precision)
            fetch_dtype=np.float64,
        )

    def transform_schema(self, columns):
        """Output schema check: appends outputCol, k-sized vectors
        (``RapidsPCA.scala:193-200``)."""
        out = list(columns)
        if self.getOutputCol() in out:
            raise ValueError(f"output column {self.getOutputCol()!r} already exists")
        out.append(self.getOutputCol())
        return out

    # -- persistence ------------------------------------------------------
    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_pca_model

        save_pca_model(self, path, overwrite=overwrite)

    def write(self) -> "_PCAModelWriter":
        return _PCAModelWriter(self)

    @staticmethod
    def load(path: str) -> "PCAModel":
        from spark_rapids_ml_tpu.io.persistence import load_pca_model

        return load_pca_model(path)

    @staticmethod
    def read() -> "_PCAModelReader":
        return _PCAModelReader()


class _PCAModelWriter:
    """``model.write().overwrite().save(path)`` fluency, as Spark MLWriter."""

    def __init__(self, model: PCAModel):
        self._model = model
        self._overwrite = False

    def overwrite(self) -> "_PCAModelWriter":
        self._overwrite = True
        return self

    def save(self, path: str) -> None:
        self._model.save(path, overwrite=self._overwrite)


class _PCAModelReader:
    def load(self, path: str) -> PCAModel:
        return PCAModel.load(path)
