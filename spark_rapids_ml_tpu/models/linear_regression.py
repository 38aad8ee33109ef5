"""LinearRegression Estimator / Model (normal-equations solver).

Spark ``org.apache.spark.ml.regression.LinearRegression`` param surface
subset: featuresCol(=inputCol), labelCol, predictionCol, fitIntercept,
regParam (L2), solver fixed to "normal" — the shape that maps onto the
partial-aggregate + small-dense-solve pattern shared with PCA
(SURVEY.md §7 step 6). Accelerated path: sufficient statistics on the MXU +
Cholesky solve in one program (``ops/linreg_kernel.py``); host fallback via
NumPy with identical math.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.obs import observed_transform, observed_fit
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


class LinearRegressionParams(HasInputCol, HasDeviceId, HasWeightCol):
    labelCol = Param("labelCol", "label column name", "label")
    elasticNetParam = Param(
        "elasticNetParam",
        "L1/L2 mix in [0,1]: penalty = regParam*(a*||w||_1 + (1-a)/2*||w||^2). "
        "0 = pure ridge (closed-form normal equations); >0 solved by FISTA "
        "on the same sufficient statistics (works on every fit path, "
        "intercept unpenalized, matching Spark/sklearn conventions)",
        0.0,
        validator=lambda v: 0.0 <= float(v) <= 1.0,
    )
    predictionCol = Param("predictionCol", "prediction output column",
                          "prediction")
    fitIntercept = Param("fitIntercept", "whether to fit an intercept", True,
                         validator=lambda v: isinstance(v, bool))
    regParam = Param("regParam", "L2 regularization strength lambda", 0.0,
                     validator=lambda v: v >= 0)
    useXlaDot = Param(
        "useXlaDot",
        "solve on the accelerator (True) or host NumPy (False)",
        True, validator=lambda v: isinstance(v, bool))
    dtype = Param("dtype", "device compute dtype", "auto",
                  validator=lambda v: v in ("auto", "float32", "float64"))


class LinearRegression(LinearRegressionParams):
    """``LinearRegression().setRegParam(0.1).fit(df)``; df needs features +
    label columns."""

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_params

        save_params(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "LinearRegression":
        from spark_rapids_ml_tpu.io.persistence import load_params

        return load_params(LinearRegression, path)

    @observed_fit("linreg")
    def fit(self, dataset, labels=None) -> "LinearRegressionModel":
        """``dataset`` may carry the label column, or pass ``labels``
        explicitly alongside a bare feature matrix. Out-of-core: ``dataset``
        may also be a generator (or zero-arg callable producing one) of
        ``(X_chunk, y_chunk)`` pairs — sufficient statistics stream through
        the device with bounded memory."""
        timer = PhaseTimer()
        source = _streaming_xy_source(dataset, labels)
        if source is not None:
            if self.getWeightCol():
                raise ValueError(
                    "weightCol is not supported with streamed/out-of-core "
                    "input yet; fit in-memory or drop the weights"
                )
            coef, intercept = self._fit_streamed(source, timer)
        else:
            frame = as_vector_frame(dataset, self.getInputCol())
            with timer.phase("densify"):
                x = frame.vectors_as_matrix(self.getInputCol())
                if labels is not None:
                    y = np.asarray(labels, dtype=np.float64).reshape(-1)
                else:
                    y = np.asarray(frame.column(self.getLabelCol()),
                                   dtype=np.float64)
            if y.shape[0] != x.shape[0]:
                raise ValueError(
                    f"labels length {y.shape[0]} != rows {x.shape[0]}"
                )
            weights = self._extract_weights(frame, x.shape[0])
            from spark_rapids_ml_tpu.data.batches import stream_threshold_bytes

            if (
                self.getUseXlaDot()
                and weights is None
                and x.nbytes > stream_threshold_bytes()
            ):
                source = _xy_batch_source(x, y)
                coef, intercept = self._fit_streamed(source, timer)
            elif self.getUseXlaDot():
                coef, intercept = self._fit_xla(x, y, timer, weights)
            else:
                coef, intercept = self._fit_host(x, y, timer, weights)
        model = LinearRegressionModel(
            coefficients=np.asarray(coef, dtype=np.float64),
            intercept=float(intercept),
        )
        model.uid = self.uid
        model.copy_values_from(self)
        model.fit_timings_ = timer.as_dict()
        return model

    def _fit_streamed(self, source, timer):
        """One pass of Z=[X|y] sufficient statistics (ZᵀZ, Σz, n) — on the
        device accumulator when ``useXlaDot``, NumPy float64 otherwise —
        then the tiny (n_features+1) normal-equations solve on host in
        float64. Mathematically identical to the one-shot kernel; memory is
        one batch + one (n+1)² Gram."""
        nz = source.n_features  # n_features + 1 (label column)
        if self.getUseXlaDot():
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.models.pca import (
                _resolve_device,
                _resolve_dtype,
            )
            from spark_rapids_ml_tpu.ops.streaming import init_stats, update_stats

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            with timer.phase("fit_kernel"), TraceRange(
                "linreg streamed", TraceColor.GREEN
            ):
                stats = init_stats(nz, dtype=dtype, device=device)
                for batch, mask in source.batches():
                    stats = update_stats(
                        stats, jnp.asarray(batch, dtype=dtype),
                        None if mask is None else jnp.asarray(mask))
                g = np.asarray(stats.gram, dtype=np.float64)
                s = np.asarray(stats.col_sum, dtype=np.float64)
                cnt = float(stats.count)
        else:
            with timer.phase("fit_kernel"), TraceRange(
                "linreg host", TraceColor.ORANGE
            ):
                g = np.zeros((nz, nz))
                s = np.zeros(nz)
                cnt = 0.0
                for batch, mask in source.batches():
                    b = np.asarray(batch if mask is None else batch[mask],
                                   dtype=np.float64)
                    g += b.T @ b
                    s += b.sum(axis=0)
                    cnt += b.shape[0]
        if cnt < 1:
            raise ValueError("empty dataset")
        n = nz - 1
        return self._solve_from_raw_moments(
            g[:n, :n], g[:n, n], s[:n], s[n], cnt
        )

    def _fit_xla(self, x, y, timer, weights=None):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.linreg_kernel import (
            linreg_fit_kernel,
            linreg_partial_stats_kernel,
        )

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        with timer.phase("h2d"):
            x_dev = jax.device_put(jnp.asarray(x, dtype=dtype), device)
            y_dev = jax.device_put(jnp.asarray(y, dtype=dtype), device)
            # the kernel's mask slot IS a general per-row weight: every
            # statistic it folds is Σ mᵢ·(…) — exactly weighted least
            # squares (Spark's weightCol semantics)
            w_dev = (
                None
                if weights is None
                else jax.device_put(jnp.asarray(weights, dtype=dtype), device)
            )
        if float(self.getElasticNetParam()) > 0.0 and float(self.getRegParam()) > 0.0:
            # L1 has no closed form: the MXU builds the (XᵀWX, XᵀWy)
            # stats; the tiny d-dimensional FISTA runs on host f64
            with timer.phase("fit_kernel"), TraceRange(
                "linreg stats", TraceColor.GREEN
            ):
                stats = jax.block_until_ready(
                    linreg_partial_stats_kernel(x_dev, y_dev, w_dev)
                )
            return self._solve_from_raw_moments(
                np.asarray(stats.xtx, dtype=np.float64),
                np.asarray(stats.xty, dtype=np.float64),
                np.asarray(stats.x_sum, dtype=np.float64),
                float(stats.y_sum),
                float(stats.count),
            )
        with timer.phase("fit_kernel"), TraceRange("linreg normal", TraceColor.GREEN):
            result = jax.block_until_ready(
                linreg_fit_kernel(
                    x_dev, y_dev, w_dev,
                    reg_param=float(self.getRegParam()),
                    fit_intercept=self.getFitIntercept(),
                )
            )
        return result.coefficients, result.intercept

    def _fit_host(self, x, y, timer, weights=None):
        with timer.phase("fit_kernel"), TraceRange("linreg host", TraceColor.ORANGE):
            w = np.ones(x.shape[0]) if weights is None else np.asarray(weights)
            xw = x * w[:, None]
            coef, intercept = self._solve_from_raw_moments(
                x.T @ xw, xw.T @ y, xw.sum(axis=0), (w * y).sum(), w.sum()
            )
        return coef, intercept

    def _solve_moments(self, a, b):
        """Centered moments → coefficients: closed-form ridge, or FISTA
        when elasticNetParam > 0 brings in the L1 term."""
        lam = float(self.getRegParam())
        alpha = float(self.getElasticNetParam())
        if alpha > 0.0 and lam > 0.0:
            return _elastic_net_solve(a, b, lam, alpha)
        return np.linalg.solve(a + lam * np.eye(a.shape[0]), b)

    def _solve_from_raw_moments(self, gxx, gxy, x_sum, y_sum, cnt):
        """Raw (XᵀWX, XᵀWy, Σwx, Σwy, Σw) → (coef, intercept): the ONE
        center → solve → intercept sequence every fit path funnels into."""
        a, b, mu_x, mu_y = _centered_moments(
            gxx, gxy, x_sum, y_sum, cnt, self.getFitIntercept()
        )
        coef = self._solve_moments(a, b)
        intercept = mu_y - mu_x @ coef if self.getFitIntercept() else 0.0
        return coef, intercept


def _elastic_net_solve(a, b, lam, alpha, max_iter=500, tol=1e-8,
                       penalty_mask=None):
    """FISTA on a quadratic model: min_w  ½wᵀAw − bᵀw
    + lam·(alpha·‖w∘m‖₁ + (1−alpha)/2·‖w∘m‖²). A is d×d — the iteration
    is a tiny host loop; the MXU work (building A) already happened.
    ``penalty_mask`` (0/1 per coordinate, default all-ones) exempts
    coordinates — e.g. an unpenalized intercept slot in the prox-Newton
    logistic subproblem.
    """
    m = np.ones(a.shape[0]) if penalty_mask is None else penalty_mask
    l1 = lam * alpha * m
    l2 = lam * (1.0 - alpha) * m
    # Lipschitz constant of the smooth part: exact λmax(A) + l2. A is a
    # tiny d×d host matrix, so eigvalsh is cheap AND safe — a power
    # iteration seeded with a fixed vector diverges when that vector is
    # (near-)orthogonal to the top eigenvector (e.g. negative-
    # equicorrelation Grams, where ones IS the bottom eigenvector).
    lip = float(np.linalg.eigvalsh(a)[-1]) + float(np.max(l2)) + 1e-12

    def grad(w):
        return a @ w - b + l2 * w

    w = np.zeros(a.shape[0])
    z = w.copy()
    t = 1.0
    for _ in range(max_iter):
        g = grad(z)
        w_new = z - g / lip
        w_new = np.sign(w_new) * np.maximum(np.abs(w_new) - l1 / lip, 0.0)
        t_new = (1.0 + np.sqrt(1.0 + 4.0 * t * t)) / 2.0
        z = w_new + ((t - 1.0) / t_new) * (w_new - w)
        if np.max(np.abs(w_new - w)) <= tol:
            w = w_new
            break
        w, t = w_new, t_new
    return w


def _centered_moments(gxx, gxy, x_sum, y_sum, cnt, fit_intercept):
    """(A, b, μx, μy) from raw second moments; A/b are the centered
    (1/n)-scaled normal-equation operands shared by ridge and FISTA."""
    if fit_intercept:
        mu_x, mu_y = x_sum / cnt, y_sum / cnt
        a = gxx / cnt - np.outer(mu_x, mu_x)
        b = gxy / cnt - mu_x * mu_y
    else:
        mu_x = np.zeros(gxx.shape[0])
        mu_y = 0.0
        a = gxx / cnt
        b = gxy / cnt
    return a, b, mu_x, mu_y


def _extract_weights(est, frame, n_rows):
    """Back-compat alias: the validation lives on ``HasWeightCol``."""
    return est._extract_weights(frame, n_rows)


def _zip_xy(chunk):
    """(X_chunk, y_chunk) → the chunk Z = [X | y] as column blocks
    (``data.batches.ColumnBlocks``), never a copy of the whole chunk: a
    traced walk puts a batch that is a row slice of it as its X and y and
    joins them on the chip (``ops.streaming.IngestTrace.put``); any other
    batch is joined by the source's one host copy into the batch."""
    from spark_rapids_ml_tpu.data.batches import ColumnBlocks

    if not (isinstance(chunk, tuple) and len(chunk) == 2):
        raise ValueError(
            "streamed LinearRegression chunks must be (X, y) tuples"
        )
    x, y = chunk
    # arrays are the caller's rows as they are; a list is densified here
    viewed = isinstance(x, np.ndarray) and isinstance(y, np.ndarray)
    x = np.asarray(x)
    if x.ndim == 1:
        x = x[None, :]
    y = np.asarray(y)
    # Promote to a common float dtype (at least f32) — casting y to x's
    # dtype would silently floor float labels when X chunks are integer.
    dt = np.promote_types(np.result_type(x.dtype, y.dtype), np.float32)
    if y.size != x.shape[0]:
        raise ValueError(
            f"chunk labels length {y.size} != chunk rows {x.shape[0]}"
        )
    return ColumnBlocks((x, y.reshape(-1, 1)), dt, viewed)


def _streaming_xy_source(dataset, labels, batch_rows: int = 0):
    """BatchSource over Z=[X|y] for generator/callable inputs, else None;
    ``batch_rows`` rows a batch (0: auto-sized, ≈128 MiB).

    The user's callable/iterator goes to BatchSource UNWRAPPED (``_zip_xy``
    rides along as ``chunk_transform``) so the non-fresh-factory detection
    in ``BatchSource.__init__`` still sees the underlying iterator."""
    from spark_rapids_ml_tpu.data.batches import BatchSource

    if labels is None and (callable(dataset) or hasattr(dataset, "__next__")):
        return BatchSource(dataset, batch_rows=batch_rows,
                           chunk_transform=_zip_xy)
    return None


def _xy_batch_source(x: np.ndarray, y: np.ndarray, batch_rows: int = 0,
                     weights: Optional[np.ndarray] = None):
    """Re-iterable Z=[X|y] source over in-memory arrays, chunk-wise (no
    whole-matrix hstack copy): chunks of ``batch_rows`` rows (0:
    auto-sized, ≈128 MiB; at most the rows there are), so a traced walk's
    every full batch is a row slice of one chunk, put as its X and y and
    joined on the chip. ``weights``: the rows' (``BatchSource``)."""
    from spark_rapids_ml_tpu.data.batches import BatchSource, auto_batch_rows

    rows = min(batch_rows or auto_batch_rows(x.shape[1] + 1),
               max(1, x.shape[0]))

    def chunks():
        for i in range(0, x.shape[0], rows):
            yield (x[i:i + rows], y[i:i + rows])

    return BatchSource(chunks, batch_rows=rows, n_features=x.shape[1] + 1,
                       chunk_transform=_zip_xy, weights=weights)


class LinearRegressionModel(LinearRegressionParams):
    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 intercept: float = 0.0, uid: Optional[str] = None):
        super().__init__(uid=uid)
        self.coefficients = coefficients
        self.intercept = intercept
        self.fit_timings_ = {}

    def _copy_internal_state(self, other: "LinearRegressionModel") -> None:
        other.coefficients = self.coefficients
        other.intercept = self.intercept

    @observed_transform
    def transform(self, dataset) -> VectorFrame:
        if self.coefficients is None:
            raise ValueError("model has no coefficients; fit first or load")
        frame = as_vector_frame(dataset, self.getInputCol())
        x = frame.vectors_as_matrix(self.getInputCol())
        if self.getUseXlaDot():
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops.linreg_kernel import linreg_predict_kernel

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            pred = np.asarray(
                linreg_predict_kernel(
                    jax.device_put(jnp.asarray(x, dtype=dtype), device),
                    jnp.asarray(self.coefficients, dtype=dtype),
                    jnp.asarray(self.intercept, dtype=dtype),
                )
            )
        else:
            pred = x @ self.coefficients + self.intercept
        return frame.with_column(
            self.getPredictionCol(), pred.astype(np.float64)
        )

    def evaluate(self, dataset, labels=None) -> dict:
        """RMSE / MSE / R² summary (Spark's LinearRegressionSummary core)."""
        frame = as_vector_frame(dataset, self.getInputCol())
        x = frame.vectors_as_matrix(self.getInputCol())
        if labels is not None:
            y = np.asarray(labels, dtype=np.float64).reshape(-1)
        else:
            y = np.asarray(frame.column(self.getLabelCol()), dtype=np.float64)
        pred = x @ self.coefficients + self.intercept
        resid = y - pred
        mse = float((resid**2).mean())
        ss_tot = float(((y - y.mean()) ** 2).sum())
        r2 = 1.0 - float((resid**2).sum()) / ss_tot if ss_tot > 0 else 0.0
        return {"mse": mse, "rmse": mse**0.5, "r2": r2}

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_linreg_model

        save_linreg_model(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "LinearRegressionModel":
        from spark_rapids_ml_tpu.io.persistence import load_linreg_model

        return load_linreg_model(path)
