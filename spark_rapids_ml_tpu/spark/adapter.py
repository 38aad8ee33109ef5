"""Generic DataFrame front-ends for the rest of the model family.

The reference advertises a one-import-change drop-in over Spark DataFrames
(``/root/reference/README.md:12-28``); the sufficient-statistics families
(PCA, LinearRegression, LogisticRegression, KMeans) have bespoke
``mapInArrow`` planes in ``spark/estimator.py``. The families whose fits
are NOT small-combinable-statistics shaped (forests boost/grow against the
whole device-resident dataset; KNN indexes all items) ride THIS generic
adapter instead: ``fit`` gathers the selected columns to the driver and
runs the local estimator on the driver's accelerator — the same
"heavy solve on the driver's device" posture as the reference's driver-GPU
``calSVD`` (``RapidsRowMatrix.scala:94-95``) — and ``transform`` runs the
fitted model per Arrow batch inside a ``pandas_udf`` on executors (model
shipped by closure, the broadcast-small-state pattern of
``RapidsRowMatrix.scala:162-166``).

Scale note, stated rather than hidden: ``fit`` materializes the selected
columns on the driver, so the input must fit in driver memory — the
documented envelope for these families this round; the statistics families
stream. ``transform`` is constant-memory per batch on executors.

Works identically against real pyspark and the in-repo local engine
(``spark/_compat.py``).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Type

import numpy as np

from spark_rapids_ml_tpu.spark._compat import (
    DenseVector,
    Estimator,
    Model,
    VectorUDT,
    pandas_udf,
)
from spark_rapids_ml_tpu.obs import observed_transform

__all__ = [
    "GBTClassifier",
    "GBTRegressor",
    "LinearSVC",
    "RobustScaler",
    "RobustScalerModel",
    "Imputer",
    "ImputerModel",
    "MaxAbsScaler",
    "MinMaxScaler",
    "NaiveBayesModel",
    "NearestNeighbors",
    "OneVsRest",
    "UMAP",
    "RandomForestClassifier",
    "RandomForestRegressor",
    "StandardScaler",
    "TruncatedSVD",
]


# Driver-collect envelope (rows). The generic adapter materializes the
# selected columns on the driver — correct for the non-decomposable fits
# it serves (e.g. UMAP's spectral init) but bounded by driver memory, the
# same envelope convention the local models document (models/dbscan.py).
# Families with executor statistics planes (PCA/LinReg/LogReg/KMeans/
# NaiveBayes/RandomForest/GBT in spark/estimator.py) never pass through
# here and have no such bound.
_COLLECT_WARN_ROWS = int(
    os.environ.get("SPARK_RAPIDS_ML_TPU_COLLECT_WARN_ROWS", 1_000_000)
)
_COLLECT_MAX_ROWS = int(
    os.environ.get("SPARK_RAPIDS_ML_TPU_COLLECT_MAX_ROWS", 10_000_000)
)


def _check_collect_envelope(dataset, est_name: str) -> None:
    """Count rows before a driver collect; warn past the soft envelope,
    raise past the hard one (both configurable via env)."""
    try:
        n = int(dataset.count())
    except Exception:  # noqa: BLE001 - a frame without count() collects as-is
        return
    if n > _COLLECT_MAX_ROWS:
        raise ValueError(
            f"{est_name}.fit would collect {n:,} rows onto the driver "
            f"(envelope: {_COLLECT_MAX_ROWS:,}, "
            "SPARK_RAPIDS_ML_TPU_COLLECT_MAX_ROWS). At this scale use a "
            "statistics-plane family (PCA, LinearRegression, "
            "LogisticRegression, KMeans, NaiveBayes, RandomForest, GBT) "
            "whose executors reduce partials instead of shipping rows, "
            "or downsample the DataFrame first."
        )
    if n > _COLLECT_WARN_ROWS:
        import warnings

        warnings.warn(
            f"{est_name}.fit collects {n:,} rows onto the driver "
            f"(soft envelope {_COLLECT_WARN_ROWS:,}; hard cap "
            f"{_COLLECT_MAX_ROWS:,} via "
            "SPARK_RAPIDS_ML_TPU_COLLECT_MAX_ROWS)",
            ResourceWarning,
            stacklevel=3,
        )


def _densify(series) -> np.ndarray:
    return np.stack([
        v.toArray() if hasattr(v, "toArray")
        else np.asarray(v, dtype=np.float64)
        for v in series
    ])


class _AdapterEstimator(Estimator):
    """``fit(df)`` → driver-collect → local estimator on the driver's
    accelerator. Subclasses set ``_local_cls``/``_model_cls`` and whether a
    label column participates. Param names forward to the local estimator
    (``featuresCol`` aliases the local ``inputCol``), so the full local
    param surface (numTrees, smoothing, algorithm, ...) is reachable."""

    _local_cls: Optional[Type] = None
    _model_cls: Optional[Type] = None
    _needs_label = False
    _aliases: Dict[str, str] = {"featuresCol": "inputCol"}
    # local param names whose values (when set) name additional scalar
    # columns the fit consumes (e.g. AFT's censorCol)
    _extra_scalar_cols: tuple = ()

    def __init__(self, **kwargs):
        super().__init__()
        self._local = self._local_cls()
        for name, value in kwargs.items():
            self._set_local(name, value)

    # -- param forwarding --------------------------------------------------
    def _set_local(self, name: str, value):
        local_name = self._aliases.get(name, name)
        if not self._local.has_param(local_name):
            raise ValueError(
                f"{type(self).__name__} has no param {name!r}"
            )
        self._local.set(local_name, value)

    def _get_local(self, name: str):
        return self._local.get_or_default(self._aliases.get(name, name))

    def __getattr__(self, attr: str):
        if attr.startswith("set") and len(attr) > 3:
            name = attr[3].lower() + attr[4:]
            return lambda value: (self._set_local(name, value), self)[1]
        if attr.startswith("get") and len(attr) > 3:
            name = attr[3].lower() + attr[4:]
            return lambda: self._get_local(name)
        raise AttributeError(attr)

    @property
    def featuresCol(self) -> str:
        return self._local.getInputCol()

    # -- fit ---------------------------------------------------------------
    def _collect_frame(self, dataset):
        from spark_rapids_ml_tpu.data.frame import as_vector_frame

        _check_collect_envelope(dataset, type(self).__name__)
        fcol = self._local.getInputCol()
        cols = [fcol]
        lcol = None
        if self._needs_label:
            lcol = self._local.getLabelCol()
            cols.append(lcol)
        wcol = ""
        if self._local.has_param("weightCol"):
            wcol = self._local.get_or_default("weightCol") or ""
            if wcol:
                cols.append(wcol)
        extra = []
        for pname in self._extra_scalar_cols:
            c = self._local.get_or_default(pname) or ""
            if c:
                cols.append(c)
                extra.append(c)
        rows = dataset.select(*cols).collect()
        x = np.stack([
            r[0].toArray() if hasattr(r[0], "toArray")
            else np.asarray(r[0], dtype=np.float64)
            for r in rows
        ])
        frame = as_vector_frame(x, fcol)
        if lcol is not None:
            frame = frame.with_column(
                lcol, [float(r[1]) for r in rows]
            )
        if wcol:
            frame = frame.with_column(
                wcol, [float(r[cols.index(wcol)]) for r in rows]
            )
        for c in extra:
            frame = frame.with_column(
                c, [float(r[cols.index(c)]) for r in rows]
            )
        return frame

    def _fit(self, dataset):
        local_model = self._local.fit(self._collect_frame(dataset))
        return self._model_cls(local_model)

    def fit(self, dataset, params=None):
        return self._fit(dataset)

    # -- persistence -------------------------------------------------------
    def save(self, path: str, overwrite: bool = False) -> None:
        self._local.save(path, overwrite=overwrite)

    @classmethod
    def load(cls, path: str):
        out = cls()
        out._local = cls._local_cls.load(path)
        return out


def _host_fitted_state(model) -> None:
    """Convert a fitted model's device-resident jax Arrays to host numpy,
    in place. The adapter ships fitted models to executors by cloudpickle
    closure; a device-resident attribute (e.g. a forest's stacked
    ``ensemble_``) would force a device sync on the driver at pickle time
    and make every executor worker initialize an accelerator backend just
    to deserialize — and an accelerator belongs to one process. Models
    re-stage to their own device lazily on first use."""
    try:
        import jax
    except Exception:  # noqa: BLE001 - no jax, nothing device-resident
        return

    def to_host(v):
        return np.asarray(v) if isinstance(v, jax.Array) else v

    for name, value in list(vars(model).items()):
        try:
            vars(model)[name] = jax.tree_util.tree_map(to_host, value)
        except Exception:  # noqa: BLE001 - unknown containers stay as-is
            continue


class _AdapterModel(Model):
    """Wraps a fitted local model; ``transform`` ships it to executors by
    closure and appends the model's own output column per Arrow batch."""

    _local_model_cls: Optional[Type] = None
    # name of the local param holding the appended column, and its type
    _out_col_param = "predictionCol"
    _out_kind = "double"          # "double" | "vector"

    def __init__(self, local_model):
        super().__init__()
        _host_fitted_state(local_model)
        self._local = local_model

    def __getattr__(self, attr: str):
        if attr.startswith("set") and len(attr) > 3:
            name = attr[3].lower() + attr[4:]
            local = object.__getattribute__(self, "_local")
            if local.has_param(name):
                return lambda value: (local.set(name, value), self)[1]
        if attr.startswith("get") and len(attr) > 3:
            name = attr[3].lower() + attr[4:]
            local = object.__getattribute__(self, "_local")
            if local.has_param(name):
                return lambda: local.get_or_default(name)
        # expose fitted attributes (feature_importances_, classes_, ...)
        return getattr(object.__getattribute__(self, "_local"), attr)

    @observed_transform
    def _transform(self, dataset):
        local = self._local
        in_col = local.getInputCol()
        out_col = local.get_or_default(self._out_col_param)
        if not out_col:   # Spark convention: '' disables the column
            return dataset
        vector_out = self._out_kind == "vector"
        return_type = VectorUDT() if vector_out else "double"

        @pandas_udf(returnType=return_type)
        def apply_model(series):
            import pandas as pd

            x = _densify(series)
            out = local.transform(x)
            values = out.column(out_col)
            if vector_out:
                return pd.Series([DenseVector(v) for v in values])
            return pd.Series([float(v) for v in values])

        return dataset.withColumn(out_col, apply_model(dataset[in_col]))

    @observed_transform
    def transform(self, dataset, params=None):
        return self._transform(dataset)

    def save(self, path: str, overwrite: bool = False) -> None:
        self._local.save(path, overwrite=overwrite)

    @classmethod
    def load(cls, path: str):
        return cls(cls._local_model_cls.load(path))


class _ClassifierAdapterModel(_AdapterModel):
    """Classifier variant: ONE inference pass computes the probability
    column; the prediction column then derives from it with a cheap
    argmax UDF (classes_-mapped) — no second forest/model evaluation,
    matching Spark's vector probability + prediction pair. ``''`` in
    either column param disables that column (Spark convention)."""

    _proba_scalar = False   # local probabilityCol holds P(y=1) scalars

    @observed_transform
    def _transform(self, dataset):
        import numpy as np_

        local = self._local
        in_col = local.getInputCol()
        proba_col = local.get_or_default("probabilityCol")
        pred_col = local.get_or_default(self._out_col_param)
        classes = np_.asarray(
            getattr(local, "classes_", None)
            if getattr(local, "classes_", None) is not None
            else [0.0, 1.0],
            dtype=np_.float64,
        )
        scalar_proba = self._proba_scalar

        if not proba_col:
            # no probability requested: single prediction-only pass
            return super()._transform(dataset)

        @pandas_udf(returnType=VectorUDT())
        def proba_udf(series):
            import pandas as pd

            x = _densify(series)
            values = local.transform(x).column(proba_col)
            if scalar_proba:
                return pd.Series(
                    [DenseVector([1.0 - float(v), float(v)])
                     for v in values]
                )
            return pd.Series([DenseVector(v) for v in values])

        result = dataset.withColumn(proba_col, proba_udf(dataset[in_col]))
        if not pred_col:
            return result

        @pandas_udf(returnType="double")
        def pred_udf(series):
            import pandas as pd

            proba = np_.stack([v.toArray() for v in series])
            if local.has_param("thresholds"):
                idx = local._predict_index(proba)
            else:
                idx = np_.argmax(proba, axis=1)
            return pd.Series([float(classes[int(i)]) for i in idx])

        return result.withColumn(pred_col, pred_udf(result[proba_col]))


class _SVCAdapterModel(_AdapterModel):
    """LinearSVC variant: Spark's ``LinearSVCModel`` emits rawPrediction
    as the 2-vector ``[-margin, margin]`` (one score per class); the local
    model keeps the scalar margin (documented there). ONE inference pass
    computes the raw vector; the prediction column derives from it with a
    cheap margin-vs-threshold UDF. ``''`` in either column param disables
    that column (Spark convention)."""

    @observed_transform
    def _transform(self, dataset):
        local = self._local
        in_col = local.getInputCol()
        raw_col = local.get_or_default("rawPredictionCol")
        pred_col = local.get_or_default(self._out_col_param)
        thr = float(local.get_or_default("threshold"))

        if not raw_col:
            # no raw column requested: single prediction-only pass
            return super()._transform(dataset)

        @pandas_udf(returnType=VectorUDT())
        def raw_udf(series):
            import pandas as pd

            x = _densify(series)
            margins = local.decision_function(x)
            return pd.Series(
                [DenseVector([-float(m), float(m)]) for m in margins]
            )

        result = dataset.withColumn(raw_col, raw_udf(dataset[in_col]))
        if not pred_col:
            return result

        @pandas_udf(returnType="double")
        def pred_udf(series):
            import pandas as pd

            return pd.Series([
                1.0 if float(v.toArray()[1]) > thr else 0.0 for v in series
            ])

        return result.withColumn(pred_col, pred_udf(result[raw_col]))


class _GLMAdapterModel(_AdapterModel):
    """GeneralizedLinearRegression variant: ONE feature pass computes
    eta (linkPrediction); the mean prediction mu = g^-1(eta) derives
    elementwise from it without a second densify/matmul. When ``offsetCol`` is set the model REQUIRES that column at
    scoring time and adds it to eta — a deliberate deviation from Spark,
    which silently ignores the training offset at transform; silently
    dropping a fitted exposure produces wrong rates (documented in
    ``models/glm.py``)."""

    @observed_transform
    def _transform(self, dataset):
        local = self._local
        in_col = local.getInputCol()
        pred_col = local.get_or_default("predictionCol")
        link_col = local.get_or_default("linkPredictionCol")
        offset_col = local.get_or_default("offsetCol")
        if offset_col and offset_col not in dataset.columns:
            raise ValueError(
                f"offsetCol {offset_col!r} is set on the model but missing "
                "from the input DataFrame"
            )
        from spark_rapids_ml_tpu.ops.glm_kernel import link_funcs

        family, link, var_power, link_power = local._resolved_family_link()
        _, ginv, _ = link_funcs(link, link_power)
        coef = np.asarray(local.coefficients, dtype=np.float64)
        b = float(local.intercept)

        def _eta(feat_series, off_series):
            x = _densify(feat_series)
            eta = x @ coef + b
            if off_series is not None:
                eta = eta + np.asarray(off_series, dtype=np.float64)
            return eta

        def _feature_pass(col, to_mu):
            """ONE densify + matmul pass producing eta (or mu) into col."""
            if offset_col:
                @pandas_udf(returnType="double")
                def apply(feat, off):
                    import pandas as pd

                    eta = _eta(feat, off)
                    vals = ginv(np, eta) if to_mu else eta
                    return pd.Series(np.asarray(vals, dtype=np.float64))

                return dataset.withColumn(
                    col, apply(dataset[in_col], dataset[offset_col]))

            @pandas_udf(returnType="double")
            def apply(feat):
                import pandas as pd

                eta = _eta(feat, None)
                vals = ginv(np, eta) if to_mu else eta
                return pd.Series(np.asarray(vals, dtype=np.float64))

            return dataset.withColumn(col, apply(dataset[in_col]))

        if not link_col:
            return _feature_pass(pred_col, True) if pred_col else dataset
        result = _feature_pass(link_col, False)
        if not pred_col:
            return result

        # mu derives elementwise from the already-computed eta column —
        # no second densify/matmul pass (the _SVCAdapterModel pattern)
        @pandas_udf(returnType="double")
        def mu_from_eta(eta_series):
            import pandas as pd

            eta = np.asarray(eta_series, dtype=np.float64)
            return pd.Series(np.asarray(ginv(np, eta), dtype=np.float64))

        return result.withColumn(pred_col, mu_from_eta(result[link_col]))


def _make_pair(name, local_est, local_model, *, needs_label,
               out_col_param="predictionCol", out_kind="double",
               classifier=False, proba_scalar=False, aliases=None, doc="",
               model_base=None, extra_scalar_cols=()):
    base = model_base or (
        _ClassifierAdapterModel if classifier else _AdapterModel
    )
    model_cls = type(
        f"{name}Model",
        (base,),
        {
            "_local_model_cls": local_model,
            "_out_col_param": out_col_param,
            "_out_kind": out_kind,
            "_proba_scalar": proba_scalar,
            "__doc__": f"DataFrame front-end over "
                       f"``models.{local_model.__name__}``. {doc}",
        },
    )
    est_cls = type(
        name,
        (_AdapterEstimator,),
        {
            "_local_cls": local_est,
            "_model_cls": model_cls,
            "_needs_label": needs_label,
            "_aliases": aliases or {"featuresCol": "inputCol"},
            "_extra_scalar_cols": tuple(extra_scalar_cols),
            "__doc__": f"DataFrame front-end over "
                       f"``models.{local_est.__name__}``. {doc}",
        },
    )
    return est_cls, model_cls


from spark_rapids_ml_tpu.models.gbt import (  # noqa: E402
    GBTClassificationModel as _LGBTC_M,
    GBTClassifier as _LGBTC,
    GBTRegressionModel as _LGBTR_M,
    GBTRegressor as _LGBTR,
)
from spark_rapids_ml_tpu.models.linear_svc import (  # noqa: E402
    LinearSVC as _LSVC,
    LinearSVCModel as _LSVC_M,
)
from spark_rapids_ml_tpu.models.glm import (  # noqa: E402
    GeneralizedLinearRegression as _LGLM,
    GeneralizedLinearRegressionModel as _LGLM_M,
)
from spark_rapids_ml_tpu.models.gaussian_mixture import (  # noqa: E402
    GaussianMixture as _LGMM,
    GaussianMixtureModel as _LGMM_M,
)
from spark_rapids_ml_tpu.models.mlp import (  # noqa: E402
    MultilayerPerceptronClassifier as _LMLP,
    MultilayerPerceptronModel as _LMLP_M,
)
from spark_rapids_ml_tpu.models.naive_bayes import (  # noqa: E402
    NaiveBayesModel as _LNB_M,
)
from spark_rapids_ml_tpu.models.feature_scalers import (  # noqa: E402
    MaxAbsScaler as _LMAS,
    MaxAbsScalerModel as _LMAS_M,
    MinMaxScaler as _LMMS,
    MinMaxScalerModel as _LMMS_M,
    RobustScaler as _LRS,
    RobustScalerModel as _LRS_M,
)
from spark_rapids_ml_tpu.models.imputer import (  # noqa: E402
    Imputer as _LIMP,
    ImputerModel as _LIMP_M,
)
from spark_rapids_ml_tpu.models.random_forest import (  # noqa: E402
    RandomForestClassificationModel as _LRFC_M,
    RandomForestClassifier as _LRFC,
    RandomForestRegressionModel as _LRFR_M,
    RandomForestRegressor as _LRFR,
)
from spark_rapids_ml_tpu.models.scaler import (  # noqa: E402
    StandardScaler as _LSS,
    StandardScalerModel as _LSS_M,
)
from spark_rapids_ml_tpu.models.svd import (  # noqa: E402
    TruncatedSVD as _LSVD,
    TruncatedSVDModel as _LSVD_M,
)

RandomForestClassifier, RandomForestClassifierModel = _make_pair(
    "RandomForestClassifier", _LRFC, _LRFC_M, needs_label=True,
    classifier=True,
    doc="Histogram trees with MXU split search on the driver's device.",
)
RandomForestRegressor, RandomForestRegressorModel = _make_pair(
    "RandomForestRegressor", _LRFR, _LRFR_M, needs_label=True,
)
GBTClassifier, GBTClassifierModel = _make_pair(
    "GBTClassifier", _LGBTC, _LGBTC_M, needs_label=True,
    classifier=True, proba_scalar=True,
)
GBTRegressor, GBTRegressorModel = _make_pair(
    "GBTRegressor", _LGBTR, _LGBTR_M, needs_label=True,
)
# NaiveBayes model wrapper only: the ESTIMATOR lives in
# spark/estimator.py as a mapInArrow statistics plane (per-class
# count/sum/sq partials), which supersedes the driver-collect strategy
NaiveBayesModel = type(
    "NaiveBayesModel",
    (_ClassifierAdapterModel,),
    {"_local_model_cls": _LNB_M,
     "__doc__": "DataFrame front-end over models.NaiveBayesModel."},
)
LinearSVC, LinearSVCModel = _make_pair(
    "LinearSVC", _LSVC, _LSVC_M, needs_label=True,
    model_base=_SVCAdapterModel,
    doc="rawPrediction is Spark's 2-vector [-margin, margin]; prediction "
        "follows the margin-vs-threshold rule.",
)
GeneralizedLinearRegression, GeneralizedLinearRegressionModel = _make_pair(
    "GeneralizedLinearRegression", _LGLM, _LGLM_M, needs_label=True,
    model_base=_GLMAdapterModel,
    doc="IRLS fit runs on the executor statistics plane "
        "(spark/moments_estimator.py); transform emits mu and optional "
        "linkPrediction eta.",
)
GaussianMixture, GaussianMixtureModel = _make_pair(
    "GaussianMixture", _LGMM, _LGMM_M, needs_label=False,
    classifier=True,
    doc="EM fit runs on the executor statistics plane "
        "(spark/moments_estimator.py); probability holds the "
        "responsibility vector, prediction its argmax.",
)
MultilayerPerceptronClassifier, MultilayerPerceptronClassifierModel = (
    _make_pair(
        "MultilayerPerceptronClassifier", _LMLP, _LMLP_M,
        needs_label=True, classifier=True,
        doc="Full-batch L-BFGS compiles the whole training loop into one "
            "XLA program on the driver's device; fit collects under the "
            "adapter envelope (L-BFGS linesearch state does not decompose "
            "into cheap per-partition statistics jobs).",
    )
)
StandardScaler, StandardScalerModel = _make_pair(
    "StandardScaler", _LSS, _LSS_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
    aliases={"featuresCol": "inputCol", "inputCol": "inputCol"},
)
MinMaxScaler, MinMaxScalerModel = _make_pair(
    "MinMaxScaler", _LMMS, _LMMS_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
)
MaxAbsScaler, MaxAbsScalerModel = _make_pair(
    "MaxAbsScaler", _LMAS, _LMAS_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
)
RobustScaler, RobustScalerModel = _make_pair(
    "RobustScaler", _LRS, _LRS_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
    doc="Quantile-range scaling; exact quantiles on the collected fit "
        "(envelope-guarded).",
)
Imputer, ImputerModel = _make_pair(
    "Imputer", _LIMP, _LIMP_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
    doc="Per-feature missing-value replacement (mean/median/mode).",
)
TruncatedSVD, TruncatedSVDModel = _make_pair(
    "TruncatedSVD", _LSVD, _LSVD_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
    doc="Top-k singular structure on the driver's device.",
)


from spark_rapids_ml_tpu.models.umap import (  # noqa: E402
    UMAP as _LUMAP,
    UMAPModel as _LUMAP_M,
)

UMAP, UMAPModel = _make_pair(
    "UMAP", _LUMAP, _LUMAP_M, needs_label=False,
    out_col_param="outputCol", out_kind="vector",
    doc="Fit embeds the collected items on the driver's device; "
        "transform is the out-of-sample placement rule, applied per "
        "Arrow batch on executors.",
)


class OneVsRest(_AdapterEstimator):
    """DataFrame front-end over ``models.OneVsRest``: multiclass reduction
    over any local binary classifier (``spark.OneVsRest(classifier=
    LinearSVC(...)._local)`` or any ``spark_rapids_ml_tpu`` estimator)."""

    from spark_rapids_ml_tpu.models.ovr import OneVsRest as _local_cls_ref

    _local_cls = _local_cls_ref
    _needs_label = True

    def __init__(self, classifier=None, **kwargs):
        super().__init__(**kwargs)
        if classifier is not None:
            # accept either a local estimator or an adapter wrapper
            self._local.classifier = getattr(classifier, "_local",
                                             classifier)

    def _fit(self, dataset):
        local_model = self._local.fit(self._collect_frame(dataset))
        return OneVsRestModel(local_model)


class OneVsRestModel(_AdapterModel):
    from spark_rapids_ml_tpu.models.ovr import (
        OneVsRestModel as _local_model_cls_ref,
    )

    _local_model_cls = _local_model_cls_ref
    _out_col_param = "predictionCol"
    _out_kind = "double"


class NearestNeighbors(_AdapterEstimator):
    """DataFrame front-end over ``models.NearestNeighbors``: ``fit(df)``
    indexes the item vectors (brute/ivfflat/ivfpq per ``algorithm``);
    ``kneighbors(query_df)`` returns (distances, indices) arrays."""

    from spark_rapids_ml_tpu.models.nearest_neighbors import (
        NearestNeighbors as _local_cls_ref,
    )

    _local_cls = _local_cls_ref
    _needs_label = False

    def _fit(self, dataset):
        local_model = self._local.fit(self._collect_frame(dataset))
        return NearestNeighborsModel(local_model)


class NearestNeighborsModel(_AdapterModel):
    from spark_rapids_ml_tpu.models.nearest_neighbors import (
        NearestNeighborsModel as _local_model_cls_ref,
    )

    _local_model_cls = _local_model_cls_ref

    def kneighbors(self, dataset, k: Optional[int] = None):
        """(distances, indices) ndarrays for the query DataFrame's feature
        column — the batch-query shape the reference project's later
        generations expose."""
        in_col = self._local.getInputCol()
        rows = dataset.select(in_col).collect()
        queries = np.stack([
            r[0].toArray() if hasattr(r[0], "toArray")
            else np.asarray(r[0], dtype=np.float64)
            for r in rows
        ])
        return self._local.kneighbors(queries, k=k)

    def kneighbors_frame(self, dataset, k: Optional[int] = None):
        """Executor-side batch kNN: every partition runs its OWN queries
        against the broadcast fitted items (host-resident after fit, so
        closure shipping is cheap) — query rows never collect to the
        driver, the per-row (indices, distances) results come back as a
        DataFrame. Row order follows the input's partition-internal
        order, the ``mapInArrow`` contract."""
        local = self._local
        in_col = local.getInputCol()
        kk = k

        def job(batches):
            import pyarrow as pa

            from spark_rapids_ml_tpu.spark.aggregate import (
                vector_column_to_matrix,
            )

            for batch in batches:
                x = vector_column_to_matrix(batch.column(in_col))
                if x.shape[0] == 0:
                    continue
                dist, idx = local.kneighbors(x, k=kk)
                yield pa.RecordBatch.from_pylist(
                    [
                        {
                            "knn_indices": idx[i].tolist(),
                            "knn_distances": dist[i].tolist(),
                        }
                        for i in range(x.shape[0])
                    ],
                    schema=pa.schema([
                        ("knn_indices", pa.list_(pa.int64())),
                        ("knn_distances", pa.list_(pa.float64())),
                    ]),
                )

        return dataset.select(in_col).mapInArrow(
            job, "knn_indices array<bigint>, knn_distances array<double>"
        )

    @observed_transform
    def _transform(self, dataset):
        raise NotImplementedError(
            "NearestNeighborsModel has no column-appending transform; "
            "use kneighbors(query_df) or kneighbors_frame(query_df)"
        )
