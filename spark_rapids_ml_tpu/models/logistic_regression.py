"""LogisticRegression Estimator / Model (binary, L2, Newton-IRLS).

Spark ``org.apache.spark.ml.classification.LogisticRegression`` param
surface subset: featuresCol(=inputCol), labelCol, predictionCol,
probabilityCol, maxIter, tol, regParam (L2 / elasticNetParam=0),
fitIntercept — the same objective convention ((1/n)·logloss + λ/2·||w||²,
intercept unpenalized). Accelerated path: Newton-IRLS compiled into one
XLA program (``ops/logreg_kernel.py``); host fallback is a NumPy IRLS
with identical math. A binary fit over a re-iterable source of ``(X, y)``
chunks runs on the streamed loop PCA and KMeans run: the labelled rows
cross once in ``batchRows`` batches and stay on the chip where they fit,
and each Newton step sums its weighted Gram over them and solves on the
chip (``_newton_streamed_xla``).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.obs import observed_transform, observed_fit
from spark_rapids_ml_tpu.utils.numeric import sigmoid as _sigmoid

from spark_rapids_ml_tpu.data.frame import VectorFrame, as_vector_frame
from spark_rapids_ml_tpu.models.params import (
    HasDeviceId,
    HasInputCol,
    HasThresholds,
    HasWeightCol,
    Param,
)
from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype
from spark_rapids_ml_tpu.utils.timing import PhaseTimer
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

# Host spans of a streamed binary fit's Newton loop, inside ``fit:logreg``
# (opened by @observed_fit("logreg")); the ingest's own (``stream:next``,
# ``stream:put``, ``stream:landing/<id>``) are ``ops.streaming``'s. The
# benchmark lists these apart (``benchmarks/work/newton.py``), and a test
# holds the two lists equal.
SPAN_INIT = "newton:init"  # the first walk: rows put (and kept), step 1 summed
SPAN_STEP = "newton:step"  # each later step: a walk over the rows
SPAN_DISPATCH = "newton:dispatch"  # one batch's update_logreg_stats call
SPAN_SYNC = "newton:sync"  # the step's one host read: max|Δ|
NEWTON_SPANS = (SPAN_INIT, SPAN_STEP, SPAN_DISPATCH, SPAN_SYNC)
PHASE_INIT = "newton/init"
PHASE_STEP = "newton/step"
PHASE_DISPATCH = "newton/dispatch"
PHASE_SYNC = "newton/sync"


class LogisticRegressionParams(HasInputCol, HasDeviceId, HasWeightCol,
                               HasThresholds):
    labelCol = Param("labelCol", "label column name (binary 0/1)", "label")
    predictionCol = Param("predictionCol", "predicted class column",
                          "prediction")
    probabilityCol = Param("probabilityCol", "P(y=1) output column",
                           "probability")
    maxIter = Param("maxIter", "maximum Newton iterations", 100,
                    validator=lambda v: isinstance(v, int) and v >= 0)
    tol = Param("tol", "Newton step-size convergence tolerance", 1e-8,
                validator=lambda v: v >= 0)
    regParam = Param("regParam", "regularization strength lambda", 0.0,
                     validator=lambda v: v >= 0)
    elasticNetParam = Param(
        "elasticNetParam",
        "L1/L2 mixing alpha in [0, 1] (Spark semantics): 0 = pure L2 "
        "Newton-IRLS; >0 adds the L1 term, solved by proximal Newton "
        "(GLMNET shape) — each outer iteration's quadratic subproblem "
        "runs the shared FISTA with the intercept unpenalized. Binary "
        "in-memory fits only.",
        0.0,
        validator=lambda v: 0.0 <= float(v) <= 1.0,
    )
    fitIntercept = Param("fitIntercept", "whether to fit an intercept", True,
                         validator=lambda v: isinstance(v, bool))
    useXlaDot = Param(
        "useXlaDot",
        "solve on the accelerator (True) or host NumPy (False)",
        True, validator=lambda v: isinstance(v, bool))
    dtype = Param("dtype", "device compute dtype", "auto",
                  validator=lambda v: v in ("auto", "float32", "float64"))
    batchRows = Param(
        "batchRows",
        "rows per device batch of a streamed fit; 0 = auto-size so one "
        "f32 [X | y] batch is ~128 MiB",
        0, validator=lambda v: isinstance(v, int) and v >= 0)


class LogisticRegression(LogisticRegressionParams):
    """``LogisticRegression().setRegParam(0.01).fit(df)``; df carries the
    features + binary label columns (or pass ``labels=`` explicitly).
    Out-of-core: ``dataset`` may be a zero-arg callable yielding
    ``(X_chunk, y_chunk)`` pairs — re-iterable, one pass per Newton step."""

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_params

        save_params(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "LogisticRegression":
        from spark_rapids_ml_tpu.io.persistence import load_params

        return load_params(LogisticRegression, path)

    @observed_fit("logreg")
    def fit(self, dataset, labels=None) -> "LogisticRegressionModel":
        timer = PhaseTimer()
        from spark_rapids_ml_tpu.models.linear_regression import (
            _streaming_xy_source,
        )

        source = _streaming_xy_source(dataset, labels, self.getBatchRows())
        if source is not None:
            self._reject_streamed_weights()
            if (float(self.getElasticNetParam()) > 0.0
                    and float(self.getRegParam()) > 0.0):
                raise ValueError(
                    "elasticNetParam > 0 is not supported on streamed/"
                    "out-of-core fits yet; fit in-memory or set "
                    "elasticNetParam=0"
                )
            # optimistic binary first — the common case pays no extra
            # pass; Spark's family="auto" kicks in when iteration 1's
            # label validation sees more than two classes
            try:
                coef, intercept, n_iter = self._fit_streamed(source, timer)
            except _NonBinaryLabelsError:
                classes = _streamed_classes(source)
                if classes.size <= 2:
                    # two or fewer distinct values that are not {0,1}:
                    # genuinely bad binary labels, not a multiclass target
                    raise
                if classes.size > 100:
                    raise ValueError(
                        f"{classes.size} distinct label values: looks like "
                        "a continuous target, not classes (multinomial "
                        "supports up to 100)"
                    )
                return self._fit_multinomial_streamed(
                    source, classes, timer
                )
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
            if not np.isfinite(y).all():
                raise ValueError("labels must be finite")
            classes = np.unique(y)
            if classes.size > 2:
                # Spark's family="auto": more than two classes selects the
                # multinomial (softmax) objective. A cap guards against a
                # continuous target passed by mistake (the Newton system
                # is (K·(d+1))² — unbounded K would OOM, not error).
                if classes.size > 100:
                    raise ValueError(
                        f"{classes.size} distinct label values: looks like "
                        "a continuous target, not classes (multinomial "
                        "supports up to 100)"
                    )
                return self._fit_multinomial(
                    x, y, classes, weights, timer
                )
            _check_binary(y)
            alpha = float(self.getElasticNetParam())
            if alpha > 0.0 and float(self.getRegParam()) > 0.0:
                coef, intercept, n_iter = self._fit_elastic(
                    x, y, timer, weights, alpha
                )
            elif self.getUseXlaDot():
                coef, intercept, n_iter = self._fit_xla(x, y, timer, weights)
            else:
                coef, intercept, n_iter = self._fit_host(x, y, timer, weights)
        model = LogisticRegressionModel(
            coefficients=np.asarray(coef, dtype=np.float64),
            intercept=float(intercept),
        )
        model.uid = self.uid
        model.copy_values_from(self)
        model.n_iter_ = int(n_iter)
        model.fit_timings_ = timer.as_dict()
        return model

    def _fit_multinomial(self, x, y, classes, weights, timer):
        """Softmax family (Spark auto-selects it for >2 classes): full
        Newton on the K·(d+1) system, K² small MXU Grams per iteration
        (``ops.logreg_kernel.multinomial_fit_kernel``)."""
        if (float(self.getElasticNetParam()) > 0.0
                and float(self.getRegParam()) > 0.0):
            raise ValueError(
                "elasticNetParam > 0 is not supported for multinomial "
                "(>2 classes) fits yet; set elasticNetParam=0 or use "
                "OneVsRest over the binary elastic-net fit"
            )
        if not self.getUseXlaDot():
            raise ValueError(
                "multinomial (>2 classes) LogisticRegression runs on the "
                "XLA path only; set useXlaDot=True or use OneVsRest for a "
                "host-only multiclass reduction"
            )
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.logreg_kernel import (
            multinomial_fit_kernel,
        )

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        y_idx = np.searchsorted(classes, y)
        y_oh = np.eye(classes.size)[y_idx]
        with timer.phase("h2d"):
            x_dev = jax.device_put(jnp.asarray(x, dtype=dtype), device)
            yoh_dev = jax.device_put(jnp.asarray(y_oh, dtype=dtype), device)
            w_dev = (
                None
                if weights is None
                else jax.device_put(jnp.asarray(weights, dtype=dtype), device)
            )
        with timer.phase("fit_kernel"), TraceRange(
            "logreg softmax", TraceColor.GREEN
        ):
            result = jax.block_until_ready(
                multinomial_fit_kernel(
                    x_dev, yoh_dev, w_dev,
                    reg_param=float(self.getRegParam()),
                    fit_intercept=self.getFitIntercept(),
                    max_iter=self.getMaxIter(),
                    tol=float(self.getTol()),
                    n_classes=int(classes.size),
                )
            )
        model = LogisticRegressionModel(
            coefficient_matrix=np.asarray(
                result.coefficients, dtype=np.float64
            ),
            intercept_vector=np.asarray(
                result.intercepts, dtype=np.float64
            ),
            classes=classes.astype(np.float64),
        )
        model.uid = self.uid
        model.copy_values_from(self)
        model.n_iter_ = int(result.n_iter)
        model.fit_timings_ = timer.as_dict()
        return model

    def _fit_multinomial_streamed(self, source, classes, timer):
        """Softmax family out-of-core: one streamed raw-partials pass per
        Newton iteration into a donated device accumulator
        (``ops.logreg_kernel.update_multinomial_stats``); the K(d+1)
        system assembles and solves on host per iteration, through the
        same ``assemble_multinomial_system`` the in-memory kernel uses."""
        if not source.reiterable:
            raise ValueError(
                "LogisticRegression streaming requires a re-iterable "
                "source: Newton makes one pass per iteration"
            )
        if not self.getUseXlaDot():
            raise ValueError(
                "multinomial (>2 classes) LogisticRegression runs on the "
                "XLA path only; set useXlaDot=True or use OneVsRest for a "
                "host-only multiclass reduction"
            )
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.logreg_kernel import (
            assemble_multinomial_system,
            update_multinomial_stats,
        )

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        n = source.n_features - 1
        k = int(classes.size)
        dim = n + 1
        lam = float(self.getRegParam())
        fit_b = self.getFitIntercept()
        wb = np.zeros((k, dim))
        n_iter = 0
        eye_k = np.eye(k)
        with timer.phase("fit_kernel"), TraceRange(
            "logreg softmax streamed", TraceColor.GREEN
        ):
            for n_iter in range(1, self.getMaxIter() + 1):
                carry = jax.device_put(
                    (
                        jnp.zeros((k, dim), dtype=dtype),
                        jnp.zeros((k * dim, k * dim), dtype=dtype),
                        jnp.zeros((), dtype=dtype),
                    ),
                    device,
                )
                wb_dev = jnp.asarray(wb, dtype=dtype)
                for batch, mask in source.batches():
                    zb = np.asarray(batch, dtype=np.float64)
                    yb = zb[:, n]
                    idx = np.searchsorted(classes, yb)
                    if n_iter == 1:
                        real = yb if mask is None else yb[np.asarray(mask)]
                        ridx = np.searchsorted(classes, real)
                        ok = (ridx < k) & (
                            classes[np.minimum(ridx, k - 1)] == real
                        )
                        if not ok.all():
                            raise ValueError(
                                "streamed labels contain values outside "
                                "the observed class set"
                            )
                    y_oh = eye_k[np.clip(idx, 0, k - 1)]
                    carry = update_multinomial_stats(
                        carry,
                        jnp.asarray(zb[:, :n], dtype=dtype),
                        jnp.asarray(y_oh, dtype=dtype),
                        wb_dev,
                        None if mask is None else jnp.asarray(mask),
                    )
                carry = jax.block_until_ready(carry)
                gxa, h_raw, cnt = (
                    np.asarray(v, dtype=np.float64) for v in carry
                )
                g, h = assemble_multinomial_system(
                    jnp.asarray(gxa), jnp.asarray(h_raw),
                    jnp.asarray(float(cnt)), jnp.asarray(wb),
                    lam, fit_b,
                )
                step = np.linalg.solve(
                    np.asarray(h, dtype=np.float64),
                    np.asarray(g, dtype=np.float64).reshape(-1),
                ).reshape(k, dim)
                wb = wb - step
                if np.max(np.abs(step)) <= float(self.getTol()):
                    break
        model = LogisticRegressionModel(
            coefficient_matrix=wb[:, :n],
            intercept_vector=(
                wb[:, n] if fit_b else np.zeros(k)
            ),
            classes=classes.astype(np.float64),
        )
        model.uid = self.uid
        model.copy_values_from(self)
        model.n_iter_ = int(n_iter)
        model.fit_timings_ = timer.as_dict()
        return model

    def _fit_elastic(self, x, y, timer, weights, alpha):
        """Elastic-net binary fit by proximal Newton (the GLMNET shape):
        per outer iteration, the UNregularized logloss gradient/Hessian
        at (w, b) define a quadratic model whose L1/L2-penalized minimum
        is found by the shared FISTA (``linear_regression._elastic_net_
        solve``), intercept exempt. The (n+1)² model assembly reuses
        ``_assemble_newton`` with lam=0; heavy XᵀWX work runs wherever
        useXlaDot points."""
        from spark_rapids_ml_tpu.models.linear_regression import (
            _elastic_net_solve,
        )

        lam = float(self.getRegParam())
        fit_b = self.getFitIntercept()
        n = x.shape[1]
        w = np.zeros(n)
        b = 0.0
        penalty_mask = np.ones(n + 1)
        penalty_mask[n] = 0.0    # intercept unpenalized
        n_iter = 0
        use_xla = self.getUseXlaDot()
        if use_xla:
            import jax
            import jax.numpy as jnp

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            with timer.phase("h2d"):
                z_np = np.concatenate([x, y.reshape(-1, 1)], axis=1)
                z_dev = jax.device_put(jnp.asarray(z_np, dtype=dtype),
                                       device)
                w_mask = (
                    None if weights is None
                    else jax.device_put(jnp.asarray(weights, dtype=dtype),
                                        device)
                )
        with timer.phase("fit_kernel"), TraceRange(
            "logreg elastic", TraceColor.GREEN
        ):
            for n_iter in range(1, self.getMaxIter() + 1):
                if use_xla:
                    g, h = _xla_logloss_grad_hess(
                        z_dev, w, b, w_mask, device, dtype, fit_b
                    )
                else:
                    g, h = _full_grad_hess(x, y, w, b, 0.0, fit_b, weights)
                # curvature floor: on (near-)separable data the IRLS
                # weights underflow and the lam=0 Hessian collapses,
                # leaving the L1 subproblem unbounded along the
                # unpenalized intercept; a scale-aware ridge keeps every
                # FISTA subproblem strongly convex (GLMNET's damping role)
                ridge = 1e-6 * max(1.0, float(np.trace(h)) / h.shape[0])
                h = h + ridge * np.eye(h.shape[0])
                wb = np.concatenate([w, [b]])
                # quadratic model around wb: ½w̃ᵀHw̃ − (Hwb − g)ᵀw̃
                target = h @ wb - g
                wb_new = _elastic_net_solve(
                    h, target, lam, alpha,
                    penalty_mask=penalty_mask,
                )
                step = np.max(np.abs(wb_new - wb))
                w = wb_new[:n]
                b = float(wb_new[n]) if fit_b else 0.0
                if step <= float(self.getTol()):
                    break
        return w, b, n_iter

    def _fit_xla(self, x, y, timer, weights=None):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.logreg_kernel import logreg_fit_kernel

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        with timer.phase("h2d"):
            x_dev = jax.device_put(jnp.asarray(x, dtype=dtype), device)
            y_dev = jax.device_put(jnp.asarray(y, dtype=dtype), device)
            # the kernel's mask multiplies residual, IRLS weights, and the
            # count — exactly the weighted MLE (Spark's weightCol)
            w_dev = (
                None
                if weights is None
                else jax.device_put(jnp.asarray(weights, dtype=dtype), device)
            )
        with timer.phase("fit_kernel"), TraceRange("logreg newton", TraceColor.GREEN):
            result = jax.block_until_ready(
                logreg_fit_kernel(
                    x_dev, y_dev, w_dev,
                    reg_param=float(self.getRegParam()),
                    fit_intercept=self.getFitIntercept(),
                    max_iter=self.getMaxIter(),
                    tol=float(self.getTol()),
                )
            )
        return result.coefficients, result.intercept, result.n_iter

    def _fit_host(self, x, y, timer, weights=None):
        """NumPy Newton-IRLS, same objective and update rule."""
        with timer.phase("fit_kernel"), TraceRange("logreg host", TraceColor.ORANGE):
            coef, intercept, n_iter = _host_newton(
                lambda w, b: _full_grad_hess(
                    x, y, w, b, float(self.getRegParam()),
                    self.getFitIntercept(), weights,
                ),
                x.shape[1],
                self.getMaxIter(),
                float(self.getTol()),
                self.getFitIntercept(),
            )
        return coef, intercept, n_iter

    def _fit_streamed(self, source, timer):
        """Newton with one walk over a re-iterable source a step: on the
        streamed loop (``_newton_streamed_xla``), or in NumPy float64
        with ``useXlaDot`` off."""
        if not source.reiterable:
            raise ValueError(
                "LogisticRegression streaming requires a re-iterable source "
                "(a zero-arg callable returning a fresh chunk iterator): "
                "Newton makes one pass per iteration"
            )
        if self.getUseXlaDot():
            return self._newton_streamed_xla(source, timer)
        n = source.n_features - 1     # the last column is the label
        lam = float(self.getRegParam())
        fit_b = self.getFitIntercept()

        def grad_hess(w, b):
            carry = [np.zeros(n), np.zeros((n, n)), np.zeros(n),
                     0.0, 0.0, 0.0]
            for batch, mask in source.batches():
                zb = np.asarray(batch if mask is None else batch[mask],
                                dtype=np.float64)
                xb, yb = zb[:, :n], zb[:, n]
                # labels checked on every walk: the host plane is the slow
                # one anyway, and the first walk is the one that raises
                _check_binary(yb)
                p = _sigmoid(xb @ w + b)
                r = p - yb
                s = p * (1.0 - p)
                carry[0] += xb.T @ r
                carry[1] += xb.T @ (xb * s[:, None])
                carry[2] += xb.T @ s
                carry[3] += float(r.sum())
                carry[4] += float(s.sum())
                carry[5] += float(len(yb))
            return _assemble_newton(*carry, w, lam, fit_b)

        with timer.phase("fit_kernel"), TraceRange(
                "logreg streamed", TraceColor.ORANGE):
            return _host_newton(grad_hess, n, self.getMaxIter(),
                                float(self.getTol()), fit_b)

    def _newton_streamed_xla(self, source, timer):
        """Binary Newton on the streamed loop PCA and KMeans run. The first
        walk (``newton:init``) puts every ``[X | y]`` batch through
        ``IngestTrace.put`` (a full batch that is a row slice of one
        ``(X, y)`` chunk crosses as its X and y and is joined on the chip;
        a batch assembled from several chunks, and the padded tail, is
        joined by the source's one host copy), checks its labels on the
        host as it passes,
        keeps it on the chip while the budget has room — its reserve the
        step's own (``ops.logreg_kernel.step_reserve_bytes``) — and folds
        step 1's partials as it lands. Each later step (``newton:step``)
        walks the kept batches without letting them go and puts the rest
        again (``IngestTrace.replay(drain=False)``). A step is one
        ``update_logreg_stats`` a batch (``newton:dispatch``) into a
        donated accumulator, then ``newton_step``, which assembles the
        system and solves it on the chip, and one host read of max|Δ|
        (``newton:sync``): the steps stop at ``maxIter`` or when max|Δ| ≤
        ``tol``. w and b are fetched once, at the end. Counters under
        ``fit_report_.extra["newton"]``; the ingest's under ``["ingest"]``.
        """
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.data.batches import ColumnBlocks
        from spark_rapids_ml_tpu.obs.report import current_fit
        from spark_rapids_ml_tpu.ops import logreg_kernel as lk
        from spark_rapids_ml_tpu.ops.streaming import IngestTrace

        device = _resolve_device(self.getDeviceId())
        dtype = _resolve_dtype(self.getDtype())
        n = source.n_features - 1     # the last column is the label
        lam, fit_b = float(self.getRegParam()), self.getFitIntercept()
        ingest = IngestTrace(timer, device)
        counters = {"steps": 0, "batches_from_kept": 0,
                    "batches_put_again": 0, "sync_seconds": 0.0,
                    "solve_seconds": 0.0, "final_step_max": None,
                    # the step's weighted Gram: its column panels, and
                    # their MXU work ÷ the full n × n product's
                    "gram_panels": len(lk.gram_panels(n)),
                    "gram_work_share": lk.gram_work_share(n)}
        current_fit().note(newton=counters)  # filled as the fit goes
        ingest.allow_keep(lk.step_reserve_bytes(
            source.batch_rows, source.n_features, jnp.dtype(dtype).itemsize))

        def first_walk():
            for batch, mask in ingest.batches(source):
                # the label column alone; a slice the chip joins holds it
                # as a block of its own
                y = batch.blocks[-1] if isinstance(batch, ColumnBlocks) \
                    else batch[:, -1:]
                labels = y[:, 0] if mask is None else y[mask, 0]
                _check_binary(np.asarray(labels, dtype=np.float64))
                c, x_dev, m_dev = ingest.put(batch, mask, dtype)
                ingest.keep(c, x_dev, m_dev)
                yield x_dev, m_dev

        def later_walk():
            put_before, stepped = ingest.counters["batches"], 0
            for _, x_dev, m_dev in ingest.replay(source, dtype, drain=False):
                stepped += 1
                yield x_dev, m_dev
            put_again = ingest.counters["batches"] - put_before
            counters["batches_put_again"] += put_again
            counters["batches_from_kept"] += stepped - put_again

        coef = jnp.zeros((n,), dtype=dtype, device=device)
        b = jnp.zeros((), dtype=dtype, device=device)
        try:
            with timer.phase("fit_kernel"):
                for step in range(self.getMaxIter()):
                    first = step == 0
                    with timer.phase(PHASE_INIT if first else PHASE_STEP), \
                            ingest.walk(SPAN_INIT if first else SPAN_STEP):
                        carry = lk.init_logreg_carry(n, dtype, device)
                        for x_dev, m_dev in (first_walk() if first
                                             else later_walk()):
                            with ingest.stage(SPAN_DISPATCH, PHASE_DISPATCH):
                                carry = lk.update_logreg_stats(
                                    carry, x_dev, coef, b, m_dev)
                        coef, b, moved = lk.newton_step(
                            carry, coef, b, lam, fit_intercept=fit_b)
                        with ingest.stage(SPAN_SYNC, PHASE_SYNC):
                            jax.block_until_ready(carry)  # the step's Gram
                            summed = time.perf_counter()
                            moved = float(moved)
                        counters["solve_seconds"] += \
                            time.perf_counter() - summed
                    counters["steps"] += 1
                    counters["sync_seconds"] = timer.as_dict()[PHASE_SYNC]
                    counters["final_step_max"] = moved
                    if moved <= float(self.getTol()):
                        break
                with timer.phase("fetch"):
                    coef, b = jax.device_get((coef, b))
        finally:
            ingest.release()  # no device batch outlives the walks over it
        ingest.all_landed()  # max|Δ| was read behind every batch put
        ingest.set_data(n)
        return np.asarray(coef), float(b), counters["steps"]


def _xla_logloss_grad_hess(z_dev, w, b, w_mask, device, dtype, fit_b):
    """One full-pass UNregularized logloss (gradient, Hessian) at (w, b)
    on device — the prox-Newton model builder."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.logreg_kernel import (
        init_logreg_carry,
        update_logreg_stats,
    )

    n = z_dev.shape[1] - 1
    carry = init_logreg_carry(n, dtype, device)
    carry = jax.block_until_ready(update_logreg_stats(
        carry, z_dev, jnp.asarray(w, dtype=dtype),
        jnp.asarray(b, dtype=dtype), w_mask,
    ))
    gx, hxx, hxb, rsum, ssum, cnt = (
        np.asarray(v, dtype=np.float64) for v in carry
    )
    return _assemble_newton(
        gx, hxx, hxb, float(rsum), float(ssum), float(cnt), w, 0.0, fit_b
    )


def _streamed_classes(source) -> np.ndarray:
    """One pass over a re-iterable [X | y] source collecting the distinct
    label values (the streamed analogue of np.unique(y)); raises on
    non-finite labels like the in-memory fit does."""
    seen = set()
    for batch, mask in source.batches():
        yb = np.asarray(batch, dtype=np.float64)[:, -1]
        if mask is not None:
            yb = yb[np.asarray(mask)]
        if not np.isfinite(yb).all():
            raise ValueError("labels must be finite")
        seen.update(np.unique(yb).tolist())
        if len(seen) > 101:
            break  # enough to trigger the continuous-target guard
    return np.asarray(sorted(seen))


def class_indices(y: np.ndarray, classes: np.ndarray) -> np.ndarray:
    """Label values → indices into the sorted class set; raises when a
    value is outside it — ONE definition for every softmax plane."""
    k = classes.size
    idx = np.searchsorted(classes, y)
    ok = (idx < k) & (classes[np.minimum(idx, k - 1)] == y)
    if not ok.all():
        raise ValueError(
            "labels contain values outside the discovered class set"
        )
    return idx


def softmax_log_loss(x: np.ndarray, wb: np.ndarray, idx: np.ndarray) -> float:
    """Σ per-row softmax NLL at (K, d+1) parameters (max-shifted, clipped)
    — shared by the host and device statistics planes."""
    n = wb.shape[1] - 1
    z = x @ wb[:, :n].T + wb[:, n][None, :]
    z = z - z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    return float(-np.log(
        np.maximum(p[np.arange(len(idx)), idx], 1e-300)
    ).sum())


class _NonBinaryLabelsError(ValueError):
    """Raised by _check_binary — a subtype so the streamed fit can catch
    it and re-dispatch to the multinomial family without string
    matching."""


def _check_binary(y: np.ndarray, estimator: str = "LogisticRegression") -> None:
    bad = ~np.isin(y, (0.0, 1.0))
    if bad.any():
        raise _NonBinaryLabelsError(
            f"binary {estimator} requires 0/1 labels; found "
            f"{np.unique(y[bad])[:5]}"
        )


def _full_grad_hess(x, y, w, b, lam, fit_intercept, weights=None):
    z = x @ w + b
    p = _sigmoid(z)
    r = p - y
    s = p * (1.0 - p)
    if weights is not None:
        r = r * weights
        s = s * weights
    gx = x.T @ r
    hxx = x.T @ (x * s[:, None])
    cnt = float(len(y)) if weights is None else float(np.sum(weights))
    return _assemble_newton(
        gx, hxx, x.T @ s, float(r.sum()), float(s.sum()), cnt,
        w, lam, fit_intercept,
    )


def _assemble_newton(gx, hxx, hxb, rsum, ssum, cnt, w, lam, fit_intercept):
    """Spark-convention (1/n)-scaled gradient/Hessian with unpenalized
    intercept, shared by the host and streamed paths."""
    n = w.shape[0]
    inv_n = 1.0 / max(cnt, 1.0)
    g = np.zeros(n + 1)
    g[:n] = gx * inv_n + lam * w
    h = np.zeros((n + 1, n + 1))
    h[:n, :n] = hxx * inv_n + lam * np.eye(n)
    if fit_intercept:
        g[n] = rsum * inv_n
        h[:n, n] = hxb * inv_n
        h[n, :n] = hxb * inv_n
        h[n, n] = ssum * inv_n
    else:
        h[n, n] = 1.0
    return g, h


def _host_newton(grad_hess, n, max_iter, tol, fit_intercept):
    w = np.zeros(n)
    b = 0.0
    n_iter = 0
    for n_iter in range(1, max_iter + 1):
        g, h = grad_hess(w, b)
        delta = np.linalg.solve(h, g)
        w = w - delta[:n]
        if fit_intercept:
            b = b - delta[n]
        if np.max(np.abs(delta)) <= tol:
            break
    return w, b, n_iter


class LogisticRegressionModel(LogisticRegressionParams):
    """Binary fits populate ``coefficients``/``intercept`` (Spark's
    binary-only accessors); multinomial fits populate
    ``coefficient_matrix`` (K, d) / ``intercept_vector`` (K,) /
    ``classes_`` — mirroring Spark's coefficientMatrix/interceptVector."""

    def __init__(self, coefficients: Optional[np.ndarray] = None,
                 intercept: float = 0.0, uid: Optional[str] = None,
                 coefficient_matrix: Optional[np.ndarray] = None,
                 intercept_vector: Optional[np.ndarray] = None,
                 classes: Optional[np.ndarray] = None):
        super().__init__(uid=uid)
        self.coefficients = coefficients
        self.intercept = intercept
        self.coefficient_matrix = coefficient_matrix
        self.intercept_vector = intercept_vector
        self.classes_ = classes
        self.n_iter_ = None
        self.fit_timings_ = {}

    @property
    def num_classes(self) -> int:
        if self.coefficient_matrix is not None:
            return int(self.coefficient_matrix.shape[0])
        return 2

    def _copy_internal_state(self, other: "LogisticRegressionModel") -> None:
        other.coefficients = self.coefficients
        other.intercept = self.intercept
        other.coefficient_matrix = self.coefficient_matrix
        other.intercept_vector = self.intercept_vector
        other.classes_ = self.classes_
        other.n_iter_ = self.n_iter_

    @observed_transform
    def predict_proba(self, dataset) -> np.ndarray:
        """Binary: (n,) P(y=1). Multinomial: (n, K) softmax rows."""
        if self.coefficient_matrix is not None:
            frame = as_vector_frame(dataset, self.getInputCol())
            x = frame.vectors_as_matrix(self.getInputCol())
            z = x @ self.coefficient_matrix.T + self.intercept_vector[None, :]
            z = z - z.max(axis=1, keepdims=True)
            e = np.exp(z)
            return e / e.sum(axis=1, keepdims=True)
        if self.coefficients is None:
            raise ValueError("model has no coefficients; fit first or load")
        frame = as_vector_frame(dataset, self.getInputCol())
        x = frame.vectors_as_matrix(self.getInputCol())
        if self.getUseXlaDot():
            import jax
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops.logreg_kernel import (
                logreg_predict_kernel,
            )
            from spark_rapids_ml_tpu.utils.padding import (
                pad_to_bucket,
                transform_padding_enabled,
            )

            device = _resolve_device(self.getDeviceId())
            dtype = _resolve_dtype(self.getDtype())
            # Bucket-pad ragged batches (sigmoid(Xw+b) is row-independent)
            # so per-request batch sizes reuse compiled signatures.
            n_rows = x.shape[0]
            if transform_padding_enabled():
                x, n_rows = pad_to_bucket(x)
            proba = np.asarray(
                logreg_predict_kernel(
                    jax.device_put(jnp.asarray(x, dtype=dtype), device),
                    jnp.asarray(self.coefficients, dtype=dtype),
                    jnp.asarray(self.intercept, dtype=dtype),
                )
            )[:n_rows]
        else:
            z = x @ self.coefficients + self.intercept
            proba = _sigmoid(z)
        return proba.astype(np.float64)

    def _serving_weights(self, precision: str, device, dtype):
        """Device-staged (coefficients, [scale,] intercept) for one
        precision — shared by the standalone serving program and the
        fused-pipeline stage hook."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric_host

        b_dev = jax.device_put(
            jnp.asarray(self.intercept, dtype=dtype), device)
        if precision == "bf16":
            return (jax.device_put(jnp.asarray(
                self.coefficients, dtype=jnp.bfloat16), device), b_dev)
        if precision == "int8":
            q, scale = quantize_symmetric_host(self.coefficients)
            return (jax.device_put(jnp.asarray(q), device), scale, b_dev)
        return (jax.device_put(jnp.asarray(
            self.coefficients, dtype=dtype), device), b_dev)

    def serving_stage(self, precision: str = "native", *,
                      device=None, dtype=None):
        """Composable fused-pipeline stage: the un-jitted σ(X·w + b)
        body + staged weights. TERMINAL — probabilities are the
        pipeline's answer, not a feature column. Binary models only."""
        if (self.coefficient_matrix is not None
                or self.coefficients is None
                or not self.getUseXlaDot()):
            return None
        from spark_rapids_ml_tpu.models._serving import (
            ServingStage,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import logreg_kernel as _lk

        if device is None or dtype is None:
            device, dtype, _ = resolve_serving_context(self)
        body = _lk.SERVING_STAGE_BODIES.get(precision)
        if body is None:
            raise ValueError(f"unknown serving precision {precision!r}")
        return ServingStage(
            fn=body,
            weights=self._serving_weights(precision, device, dtype),
            algo="logistic_regression",
            terminal=True,
            fetch_dtype=np.dtype(np.float64),
        )

    def serving_transform_program(self, precision: str = "native",
                                  device=None):
        """Device-resident serving program for the pipelined batcher
        (``obs.serving.ServingProgram``): σ(X·w + b) with the weights
        staged once; the bf16/int8 variants reduce only the logit GEMM
        (the sigmoid stays f32). ``device`` pins one replica's device
        (the multi-device tier builds one program per chip). Binary
        models only — the multinomial path is a host softmax, and
        host-path models return None."""
        if (self.coefficient_matrix is not None
                or self.coefficients is None
                or not self.getUseXlaDot()):
            return None
        from spark_rapids_ml_tpu.models._serving import (
            build_serving_program,
            resolve_serving_context,
        )
        from spark_rapids_ml_tpu.ops import logreg_kernel as _lk

        device, dtype, donate = resolve_serving_context(self, device=device)
        weights = self._serving_weights(precision, device, dtype)
        return build_serving_program(
            device=device, dtype=dtype, algo="logistic_regression",
            precision=precision,
            kernels={
                "native": (_lk.logreg_predict_serve if donate
                           else _lk.logreg_predict_kernel),
                "bf16": _lk.logreg_predict_bf16,
                "int8": _lk.logreg_predict_int8,
            },
            weights=weights,
            # f64 probabilities, matching predict_proba's sync output
            fetch_dtype=np.float64,
        )

    @observed_transform
    def transform(self, dataset) -> VectorFrame:
        frame = as_vector_frame(dataset, self.getInputCol())
        proba = self.predict_proba(frame)  # reuse the built frame
        out = frame.with_column(self.getProbabilityCol(), proba.tolist())
        if self.coefficient_matrix is not None:
            pred = self.classes_[self._predict_index(proba)]
            return out.with_column(
                self.getPredictionCol(), pred.astype(np.float64).tolist()
            )
        return out.with_column(
            self.getPredictionCol(),
            self._predict_index(
                np.stack([1.0 - proba, proba], axis=1)
            ).astype(np.int32).tolist(),
        )

    def evaluate(self, dataset, labels=None) -> dict:
        """Accuracy / log-loss summary (binary or multinomial)."""
        frame = as_vector_frame(dataset, self.getInputCol())
        if labels is not None:
            y = np.asarray(labels, dtype=np.float64).reshape(-1)
        else:
            y = np.asarray(frame.column(self.getLabelCol()), dtype=np.float64)
        p = np.clip(self.predict_proba(dataset), 1e-12, 1 - 1e-12)
        if self.coefficient_matrix is not None:
            y_idx = np.searchsorted(self.classes_, y)
            if not (
                (y_idx < self.classes_.size)
                & (self.classes_[np.minimum(y_idx, self.classes_.size - 1)] == y)
            ).all():
                raise ValueError("labels contain values outside classes_")
            # accuracy follows the SAME prediction rule transform uses
            # (thresholds-aware), so reported metrics can never disagree
            # with the emitted prediction column
            acc = float((self._predict_index(p) == y_idx).mean())
            logloss = float(
                -np.log(p[np.arange(len(y_idx)), y_idx]).mean()
            )
            return {"accuracy": acc, "logLoss": logloss}
        pred = self._predict_index(np.stack([1.0 - p, p], axis=1))
        acc = float((pred == (y >= 0.5)).mean())
        logloss = float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean())
        return {"accuracy": acc, "logLoss": logloss}

    def save(self, path: str, overwrite: bool = False) -> None:
        from spark_rapids_ml_tpu.io.persistence import save_logreg_model

        save_logreg_model(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "LogisticRegressionModel":
        from spark_rapids_ml_tpu.io.persistence import load_logreg_model

        return load_logreg_model(path)
