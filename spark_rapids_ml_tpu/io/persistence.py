"""Model persistence in Spark ML's on-disk layout.

Wire-format parity with the reference's writer/reader
(``/root/reference/src/main/scala/org/apache/spark/ml/feature/RapidsPCA.scala:218-254``):

* ``path/metadata/part-00000`` — one JSON line: class, timestamp, uid,
  paramMap (Spark's ``DefaultParamsWriter.saveMetadata``);
* ``path/metadata/_SUCCESS`` — empty marker;
* ``path/data/part-00000.parquet`` — one row with columns
  ``pc`` (Spark DenseMatrix struct: type=1, numRows, numCols, values
  column-major, isTransposed=false) and ``explainedVariance`` (Spark
  DenseVector struct: type=1, values) — the same schema Spark writes, so a
  model trained here round-trips into a Spark ML reader and vice versa.

Estimators (no learned state) persist metadata only, like Spark's
``DefaultParamsWritable`` (``PCA.scala:27-37`` companion ``load``).
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import shutil
import time
import uuid
from typing import Any, Dict, Optional

import numpy as np

_FORMAT_VERSION = "1.0"


def _require_target(path: str, overwrite: bool) -> None:
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(
                f"path {path!r} already exists; use overwrite=True "
                "(Spark: .write().overwrite())"
            )
        shutil.rmtree(path)


# Spark class names for metadata, so a Spark DefaultParamsReader accepts
# the file (it asserts className and reads metadata['sparkVersion']); the
# Python class path travels in 'pythonClass'. The reference's user-facing
# class is com.nvidia.spark.ml.feature.PCA(Model) (PCA.scala:27-37).
_SPARK_CLASS_ALIASES = {
    "PCA": "org.apache.spark.ml.feature.PCA",
    "PCAModel": "org.apache.spark.ml.feature.PCAModel",
    "KMeans": "org.apache.spark.ml.clustering.KMeans",
    "KMeansModel": "org.apache.spark.ml.clustering.KMeansModel",
    "BisectingKMeans": "org.apache.spark.ml.clustering.BisectingKMeans",
    "BisectingKMeansModel":
        "org.apache.spark.ml.clustering.BisectingKMeansModel",
    "LinearRegression": "org.apache.spark.ml.regression.LinearRegression",
    "LinearRegressionModel": "org.apache.spark.ml.regression.LinearRegressionModel",
    "LogisticRegression": "org.apache.spark.ml.classification.LogisticRegression",
    "LogisticRegressionModel": "org.apache.spark.ml.classification.LogisticRegressionModel",
    "LinearSVC": "org.apache.spark.ml.classification.LinearSVC",
    "LinearSVCModel": "org.apache.spark.ml.classification.LinearSVCModel",
    "DecisionTreeClassifier":
        "org.apache.spark.ml.classification.DecisionTreeClassifier",
    "DecisionTreeClassificationModel":
        "org.apache.spark.ml.classification.DecisionTreeClassificationModel",
    "DecisionTreeRegressor":
        "org.apache.spark.ml.regression.DecisionTreeRegressor",
    "DecisionTreeRegressionModel":
        "org.apache.spark.ml.regression.DecisionTreeRegressionModel",
    "PowerIterationClustering":
        "org.apache.spark.ml.clustering.PowerIterationClustering",
    "Word2Vec": "org.apache.spark.ml.feature.Word2Vec",
    "Word2VecModel": "org.apache.spark.ml.feature.Word2VecModel",
    "BucketedRandomProjectionLSH":
        "org.apache.spark.ml.feature.BucketedRandomProjectionLSH",
    "BucketedRandomProjectionLSHModel":
        "org.apache.spark.ml.feature.BucketedRandomProjectionLSHModel",
    "MinHashLSH": "org.apache.spark.ml.feature.MinHashLSH",
    "MinHashLSHModel": "org.apache.spark.ml.feature.MinHashLSHModel",
    "DCT": "org.apache.spark.ml.feature.DCT",
    "Interaction": "org.apache.spark.ml.feature.Interaction",
    "FeatureHasher": "org.apache.spark.ml.feature.FeatureHasher",
    "VectorIndexer": "org.apache.spark.ml.feature.VectorIndexer",
    "VectorIndexerModel":
        "org.apache.spark.ml.feature.VectorIndexerModel",
    "UnivariateFeatureSelector":
        "org.apache.spark.ml.feature.UnivariateFeatureSelector",
    "UnivariateFeatureSelectorModel":
        "org.apache.spark.ml.feature.UnivariateFeatureSelectorModel",
    "RFormula": "org.apache.spark.ml.feature.RFormula",
    "RFormulaModel": "org.apache.spark.ml.feature.RFormulaModel",
    "FPGrowth": "org.apache.spark.ml.fpm.FPGrowth",
    "FPGrowthModel": "org.apache.spark.ml.fpm.FPGrowthModel",
    "PrefixSpan": "org.apache.spark.ml.fpm.PrefixSpan",
    "LDA": "org.apache.spark.ml.clustering.LDA",
    "LDAModel": "org.apache.spark.ml.clustering.LocalLDAModel",
    "ALS": "org.apache.spark.ml.recommendation.ALS",
    "ALSModel": "org.apache.spark.ml.recommendation.ALSModel",
    "Pipeline": "org.apache.spark.ml.Pipeline",
    "PipelineModel": "org.apache.spark.ml.PipelineModel",
    "GeneralizedLinearRegression":
        "org.apache.spark.ml.regression.GeneralizedLinearRegression",
    "GeneralizedLinearRegressionModel":
        "org.apache.spark.ml.regression.GeneralizedLinearRegressionModel",
    "MultilayerPerceptronClassifier":
        "org.apache.spark.ml.classification.MultilayerPerceptronClassifier",
    "MultilayerPerceptronModel":
        "org.apache.spark.ml.classification."
        "MultilayerPerceptronClassificationModel",
}

# Params a real Spark DefaultParamsReader recognizes per class. Extras
# (useXlaDot, deviceId, ...) would make pyspark's getAndSetParams throw
# "cannot recognize param", so they travel under the top-level
# 'tpuParamMap' key, which Spark readers ignore; our reader merges both.
_SPARK_PARAM_ALLOWLIST = {
    "PCA": {"k", "inputCol", "outputCol"},
    "PCAModel": {"k", "inputCol", "outputCol"},
    "KMeans": {"k", "maxIter", "tol", "seed", "initMode", "predictionCol",
               "weightCol"},
    "KMeansModel": {"k", "maxIter", "tol", "seed", "initMode",
                    "predictionCol", "weightCol"},
    "LinearRegression": {"labelCol", "predictionCol", "fitIntercept",
                         "regParam", "elasticNetParam", "weightCol"},
    "LinearRegressionModel": {"labelCol", "predictionCol", "fitIntercept",
                              "regParam", "elasticNetParam", "weightCol"},
    "LogisticRegression": {"labelCol", "predictionCol", "probabilityCol",
                           "maxIter", "tol", "regParam", "fitIntercept",
                           "weightCol"},
    "LogisticRegressionModel": {"labelCol", "predictionCol", "probabilityCol",
                                "maxIter", "tol", "regParam", "fitIntercept",
                                "weightCol"},
    "LinearSVC": {"labelCol", "predictionCol", "rawPredictionCol",
                  "maxIter", "tol", "regParam", "fitIntercept",
                  "standardization", "threshold", "weightCol"},
    "LinearSVCModel": {"labelCol", "predictionCol", "rawPredictionCol",
                       "maxIter", "tol", "regParam", "fitIntercept",
                       "standardization", "threshold", "weightCol"},
    "DecisionTreeClassifier": {
        "maxDepth", "maxBins", "minInstancesPerNode", "labelCol",
        "predictionCol", "probabilityCol", "seed", "weightCol"},
    "DecisionTreeClassificationModel": {
        "maxDepth", "maxBins", "minInstancesPerNode", "labelCol",
        "predictionCol", "probabilityCol", "seed", "weightCol"},
    "DecisionTreeRegressor": {
        "maxDepth", "maxBins", "minInstancesPerNode", "labelCol",
        "predictionCol", "seed", "weightCol"},
    "DecisionTreeRegressionModel": {
        "maxDepth", "maxBins", "minInstancesPerNode", "labelCol",
        "predictionCol", "seed", "weightCol"},
    "PowerIterationClustering": {
        "k", "maxIter", "initMode", "srcCol", "dstCol", "weightCol"},
    "BucketedRandomProjectionLSH": {
        "inputCol", "outputCol", "numHashTables", "bucketLength", "seed"},
    "BucketedRandomProjectionLSHModel": {
        "inputCol", "outputCol", "numHashTables", "bucketLength", "seed"},
    "MinHashLSH": {"inputCol", "outputCol", "numHashTables", "seed"},
    "MinHashLSHModel": {"inputCol", "outputCol", "numHashTables",
                        "seed"},
    "DCT": {"inputCol", "outputCol", "inverse"},
    "Interaction": {"inputCols", "outputCol"},
    "FeatureHasher": {"inputCols", "outputCol", "numFeatures",
                      "categoricalCols"},
    "VectorIndexer": {"inputCol", "outputCol", "maxCategories",
                      "handleInvalid"},
    "VectorIndexerModel": {"inputCol", "outputCol", "maxCategories",
                           "handleInvalid"},
    # NOTE: Spark's selector param is featuresCol; this repo's selector
    # convention (ChiSqSelector, VarianceThresholdSelector) is inputCol,
    # which therefore rides the Spark-visible paramMap here
    "UnivariateFeatureSelector": {
        "inputCol", "outputCol", "labelCol", "featureType",
        "labelType", "selectionMode", "selectionThreshold"},
    "UnivariateFeatureSelectorModel": {
        "inputCol", "outputCol", "labelCol", "featureType",
        "labelType", "selectionMode", "selectionThreshold"},
    "RFormula": {"formula", "featuresCol", "labelCol"},
    "RFormulaModel": {"formula", "featuresCol", "labelCol"},
    "FPGrowth": {"itemsCol", "minSupport", "minConfidence",
                 "numPartitions", "predictionCol"},
    "FPGrowthModel": {"itemsCol", "minSupport", "minConfidence",
                      "numPartitions", "predictionCol"},
    "PrefixSpan": {"minSupport", "maxPatternLength",
                   "maxLocalProjDBSize", "sequenceCol"},
    "Word2Vec": {"vectorSize", "windowSize", "minCount", "maxIter",
                 "stepSize", "seed", "maxSentenceLength", "numPartitions",
                 "inputCol", "outputCol"},
    "Word2VecModel": {"vectorSize", "windowSize", "minCount", "maxIter",
                      "stepSize", "seed", "maxSentenceLength",
                      "numPartitions", "inputCol", "outputCol"},
    "LDA": {"k", "maxIter", "optimizer", "docConcentration",
            "topicConcentration", "subsamplingRate", "learningOffset",
            "learningDecay", "optimizeDocConcentration",
            "topicDistributionCol", "seed"},
    "LDAModel": {"k", "topicDistributionCol", "seed"},
    "BisectingKMeans": {"k", "maxIter", "seed", "predictionCol",
                        "minDivisibleClusterSize", "weightCol"},
    "BisectingKMeansModel": {"k", "maxIter", "seed", "predictionCol",
                             "minDivisibleClusterSize", "weightCol"},
    "ALS": {"rank", "maxIter", "regParam", "implicitPrefs", "alpha",
            "nonnegative", "userCol", "itemCol", "ratingCol",
            "predictionCol", "coldStartStrategy", "seed",
            "numUserBlocks", "numItemBlocks"},
    "ALSModel": {"userCol", "itemCol", "predictionCol",
                 "coldStartStrategy"},
    "StandardScaler": {"withMean", "withStd", "inputCol", "outputCol"},
    "StandardScalerModel": {"withMean", "withStd", "inputCol", "outputCol"},
    "GeneralizedLinearRegression": {
        "labelCol", "predictionCol", "linkPredictionCol", "family", "link",
        "variancePower", "linkPower", "offsetCol", "maxIter", "tol",
        "regParam", "fitIntercept", "weightCol"},
    "GeneralizedLinearRegressionModel": {
        "labelCol", "predictionCol", "linkPredictionCol", "family", "link",
        "variancePower", "linkPower", "offsetCol", "maxIter", "tol",
        "regParam", "fitIntercept", "weightCol"},
    # NOTE: Spark's MLP has no weightCol param — it stays in tpuParamMap
    "MultilayerPerceptronClassifier": {
        "layers", "labelCol", "predictionCol", "probabilityCol",
        "rawPredictionCol", "maxIter", "tol", "seed", "solver",
        "stepSize", "blockSize"},
    "MultilayerPerceptronModel": {
        "layers", "labelCol", "predictionCol", "probabilityCol",
        "rawPredictionCol", "maxIter", "tol", "seed", "solver",
        "stepSize", "blockSize"},
}


def _write_metadata(path: str, cls: str, uid: str, param_map: Dict[str, Any],
                    extra: Optional[Dict[str, Any]] = None) -> None:
    meta_dir = os.path.join(path, "metadata")
    os.makedirs(meta_dir, exist_ok=True)
    simple_name = cls.rsplit(".", 1)[-1]
    allowed = _SPARK_PARAM_ALLOWLIST.get(simple_name)
    if allowed is None:
        spark_params, extra_params = param_map, {}
    else:
        spark_params = {k: v for k, v in param_map.items() if k in allowed}
        extra_params = {k: v for k, v in param_map.items() if k not in allowed}
    metadata = {
        "class": _SPARK_CLASS_ALIASES.get(simple_name, cls),
        "pythonClass": cls,
        "timestamp": int(time.time() * 1000),
        "sparkVersion": "3.1.2",  # wire-format vintage (reference pom.xml:68)
        "frameworkVersion": _FORMAT_VERSION,
        "uid": uid,
        "paramMap": spark_params,
        "defaultParamMap": {},
        "tpuParamMap": extra_params,
    }
    if extra:
        metadata["extra"] = extra
    with open(os.path.join(meta_dir, "part-00000"), "w") as f:
        f.write(json.dumps(metadata))
    open(os.path.join(meta_dir, "_SUCCESS"), "w").close()


def _read_metadata(path: str) -> Dict[str, Any]:
    with open(os.path.join(path, "metadata", "part-00000")) as f:
        return json.loads(f.readline())


def save_params(estimator, path: str, overwrite: bool = False) -> None:
    """Persist an unfitted estimator (params only)."""
    _require_target(path, overwrite)
    cls = f"{type(estimator).__module__}.{type(estimator).__qualname__}"
    _write_metadata(path, cls, estimator.uid, estimator.param_map_for_metadata())


def _restore_params(obj, meta: Dict[str, Any]):
    """Apply metadata paramMap onto a Params object (Spark's
    ``metadata.getAndSetParams``, ``RapidsPCA.scala:251``). Extension
    params live under 'tpuParamMap' (see ``_write_metadata``)."""
    for key in ("paramMap", "tpuParamMap"):
        for name, value in meta.get(key, {}).items():
            if obj.has_param(name) and value is not None:
                obj.set(name, value)
    return obj


def load_params(estimator_cls, path: str):
    meta = _read_metadata(path)
    est = estimator_cls()
    est.uid = meta["uid"]
    return _restore_params(est, meta)


# -- dense matrix/vector structs (Spark ml.linalg UDT serialized form) ----
def _dense_matrix_struct(m: np.ndarray) -> Dict[str, Any]:
    m = np.asarray(m, dtype=np.float64)
    return {
        "type": 1,
        "numRows": int(m.shape[0]),
        "numCols": int(m.shape[1]),
        "colPtrs": None,
        "rowIndices": None,
        "values": np.asfortranarray(m).ravel(order="F").tolist(),
        "isTransposed": False,
    }


def _dense_matrix_from_struct(s: Dict[str, Any]) -> np.ndarray:
    values = np.asarray(s["values"], dtype=np.float64)
    n_rows, n_cols = int(s["numRows"]), int(s["numCols"])
    if s.get("isTransposed"):
        return values.reshape(n_rows, n_cols)
    return values.reshape(n_cols, n_rows).T


def _dense_vector_struct(v: np.ndarray) -> Dict[str, Any]:
    return {
        "type": 1,
        "size": None,
        "indices": None,
        "values": np.asarray(v, dtype=np.float64).ravel().tolist(),
    }


def _dense_vector_from_struct(s: Dict[str, Any]) -> np.ndarray:
    return np.asarray(s["values"], dtype=np.float64)


def _matrix_arrow_type():
    """Spark ``MatrixUDT`` sql type: struct<type:tinyint, numRows:int,
    numCols:int, colPtrs:array<int>, rowIndices:array<int>,
    values:array<double>, isTransposed:boolean>."""
    import pyarrow as pa

    return pa.struct(
        [
            ("type", pa.int8()),
            ("numRows", pa.int32()),
            ("numCols", pa.int32()),
            ("colPtrs", pa.list_(pa.int32())),
            ("rowIndices", pa.list_(pa.int32())),
            ("values", pa.list_(pa.float64())),
            ("isTransposed", pa.bool_()),
        ]
    )


def _vector_arrow_type():
    """Spark ``VectorUDT`` sql type: struct<type:tinyint, size:int,
    indices:array<int>, values:array<double>>."""
    import pyarrow as pa

    return pa.struct(
        [
            ("type", pa.int8()),
            ("size", pa.int32()),
            ("indices", pa.list_(pa.int32())),
            ("values", pa.list_(pa.float64())),
        ]
    )


# Spark catalyst type JSON for the ml.linalg UDTs — written into the parquet
# footer under 'org.apache.spark.sql.parquet.row.metadata' so a real Spark
# reader deserializes the struct columns as Matrix/Vector values instead of
# plain Rows (the mechanism behind `spark.read.parquet(path/"data")` in
# ``RapidsPCA.scala:245-249``).
_MATRIX_UDT_JSON = {
    "type": "udt",
    "class": "org.apache.spark.ml.linalg.MatrixUDT",
    "pyClass": "pyspark.ml.linalg.MatrixUDT",
    "sqlType": {
        "type": "struct",
        "fields": [
            {"name": "type", "type": "byte", "nullable": False, "metadata": {}},
            {"name": "numRows", "type": "integer", "nullable": False,
             "metadata": {}},
            {"name": "numCols", "type": "integer", "nullable": False,
             "metadata": {}},
            {"name": "colPtrs",
             "type": {"type": "array", "elementType": "integer",
                      "containsNull": False},
             "nullable": True, "metadata": {}},
            {"name": "rowIndices",
             "type": {"type": "array", "elementType": "integer",
                      "containsNull": False},
             "nullable": True, "metadata": {}},
            {"name": "values",
             "type": {"type": "array", "elementType": "double",
                      "containsNull": False},
             "nullable": True, "metadata": {}},
            {"name": "isTransposed", "type": "boolean", "nullable": False,
             "metadata": {}},
        ],
    },
}

_VECTOR_UDT_JSON = {
    "type": "udt",
    "class": "org.apache.spark.ml.linalg.VectorUDT",
    "pyClass": "pyspark.ml.linalg.VectorUDT",
    "sqlType": {
        "type": "struct",
        "fields": [
            {"name": "type", "type": "byte", "nullable": False, "metadata": {}},
            {"name": "size", "type": "integer", "nullable": True,
             "metadata": {}},
            {"name": "indices",
             "type": {"type": "array", "elementType": "integer",
                      "containsNull": False},
             "nullable": True, "metadata": {}},
            {"name": "values",
             "type": {"type": "array", "elementType": "double",
                      "containsNull": False},
             "nullable": True, "metadata": {}},
        ],
    },
}

_SPARK_FIELD_TYPES = {
    "matrix": _MATRIX_UDT_JSON,
    "vector": _VECTOR_UDT_JSON,
    "double": "double",
    "long": "long",
    "integer": "integer",
    "boolean": "boolean",
    "array<int>": {"type": "array", "elementType": "integer",
                   "containsNull": False},
    "array<double>": {"type": "array", "elementType": "double",
                      "containsNull": False},
    "array<long>": {"type": "array", "elementType": "long",
                    "containsNull": False},
    "array<string>": {"type": "array", "elementType": "string",
                      "containsNull": True},
    "array<array<string>>": {
        "type": "array",
        "elementType": {"type": "array", "elementType": "string",
                        "containsNull": True},
        "containsNull": False},
}


def spark_row_metadata(fields) -> str:
    """Catalyst StructType JSON for ``(name, kind)`` pairs; kind is one of
    ``_SPARK_FIELD_TYPES``."""
    return json.dumps({
        "type": "struct",
        "fields": [
            {"name": name, "type": _SPARK_FIELD_TYPES[kind],
             "nullable": True, "metadata": {}}
            for name, kind in fields
        ],
    })


def _write_data_row(path: str, row: Dict[str, Any], schema=None,
                    spark_fields=None) -> None:
    """Single-row payload as Parquet (pyarrow), JSON fallback otherwise —
    the reference repartitions to 1 before writing (``RapidsPCA.scala:223``),
    so one file is exactly its on-disk shape. ``spark_fields`` adds the
    Spark row-metadata footer entry declaring UDT columns."""
    data_dir = os.path.join(path, "data")
    os.makedirs(data_dir, exist_ok=True)
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq

        table = pa.Table.from_pylist([row], schema=schema)
        if spark_fields is not None:
            table = table.replace_schema_metadata({
                "org.apache.spark.sql.parquet.row.metadata":
                    spark_row_metadata(spark_fields)
            })
        pq.write_table(table, os.path.join(data_dir, "part-00000.parquet"))
    except ImportError:  # pragma: no cover - pyarrow is baked in
        with open(os.path.join(data_dir, "part-00000.json"), "w") as f:
            json.dump(row, f)
    open(os.path.join(data_dir, "_SUCCESS"), "w").close()


def _read_data_row(path: str) -> Dict[str, Any]:
    data_dir = os.path.join(path, "data")
    pq_files = sorted(
        f for f in os.listdir(data_dir) if f.endswith(".parquet")
    )
    if pq_files:
        import pyarrow.parquet as pq

        table = pq.read_table(os.path.join(data_dir, pq_files[0]))
        return table.to_pylist()[0]
    json_files = sorted(f for f in os.listdir(data_dir) if f.endswith(".json"))
    if json_files:  # pragma: no cover
        with open(os.path.join(data_dir, json_files[0])) as f:
            return json.load(f)
    raise FileNotFoundError(f"no data payload under {data_dir}")


def save_pca_model(model, path: str, overwrite: bool = False) -> None:
    if model.pc is None:
        raise ValueError("cannot save an unfitted PCAModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "pc": _dense_matrix_struct(model.pc),
        "explainedVariance": _dense_vector_struct(model.explained_variance),
        # `mean` is an extension column (Spark stores none); readers that
        # don't know it ignore it.
        "mean": _dense_vector_struct(
            model.mean if model.mean is not None else np.zeros(model.pc.shape[0])
        ),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("pc", _matrix_arrow_type()),
                ("explainedVariance", _vector_arrow_type()),
                ("mean", _vector_arrow_type()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("pc", "matrix"), ("explainedVariance", "vector"), ("mean", "vector"),
    ])


def save_kmeans_model(model, path: str, overwrite: bool = False) -> None:
    if model.cluster_centers is None:
        raise ValueError("cannot save an unfitted KMeansModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "clusterCenters": _dense_matrix_struct(model.cluster_centers),
        "trainingCost": (
            float(model.training_cost_) if model.training_cost_ is not None else None
        ),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("clusterCenters", _matrix_arrow_type()),
                ("trainingCost", pa.float64()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("clusterCenters", "matrix"), ("trainingCost", "double"),
    ])


_FM_MODEL_CLASSES = ("FMRegressionModel", "FMClassificationModel")


def save_fm_model(model, path: str, overwrite: bool = False) -> None:
    """Spark FM model layout: (intercept, linear vector, factors
    matrix)."""
    if model.factors is None:
        raise ValueError("cannot save an unfitted FM model")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"fmClass": type(model).__qualname__,
               "numIterations": int(model.num_iterations_),
               "finalLoss": float(model.final_loss_)})
    n = model.factors.shape[0]
    linear = (model.linear if model.linear is not None
              else np.zeros(n))
    row = {
        "intercept": float(model.intercept),
        "linear": _dense_vector_struct(linear),
        "factors": _dense_matrix_struct(model.factors),
        "hasLinear": model.linear is not None,
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("intercept", pa.float64()),
            ("linear", _vector_arrow_type()),
            ("factors", _matrix_arrow_type()),
            ("hasLinear", pa.bool_()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("intercept", "double"), ("linear", "vector"),
        ("factors", "matrix"), ("hasLinear", "boolean"),
    ])


def load_fm_model(path: str):
    from spark_rapids_ml_tpu.models import fm as fm_mod

    meta = _read_metadata(path)
    name = meta.get("extra", {}).get("fmClass", "FMRegressionModel")
    if name not in _FM_MODEL_CLASSES:
        raise ValueError(
            f"{path}: unknown FM model class {name!r} "
            f"(expected one of {_FM_MODEL_CLASSES})")
    row = _read_data_row(path)
    model = getattr(fm_mod, name)(
        factors=_dense_matrix_from_struct(row["factors"]),
        linear=(_dense_vector_from_struct(row["linear"])
                if row.get("hasLinear", True) else None),
        intercept=float(row["intercept"]),
        uid=meta["uid"],
    )
    extras = meta.get("extra", {})
    model.num_iterations_ = int(extras.get("numIterations", 0))
    model.final_loss_ = float(extras.get("finalLoss", float("nan")))
    return _restore_params(model, meta)


def save_als_model(model, path: str, overwrite: bool = False) -> None:
    """Spark ALSModel layout analogue: the two factor tables plus the
    id vocabularies (Spark persists userFactors/itemFactors DataFrames;
    one row with two matrices + two id vectors is the single-file
    equivalent). Ids are float64-exact (validated < 2^53 at fit — Spark
    itself restricts ALS ids to Integer range)."""
    if model.user_factors is None:
        raise ValueError("cannot save an unfitted ALSModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"trainRmse": float(model.train_rmse_)})
    row = {
        "userFactors": _dense_matrix_struct(model.user_factors),
        "itemFactors": _dense_matrix_struct(model.item_factors),
        "userIds": _dense_vector_struct(
            np.asarray(model.user_ids, dtype=np.float64)),
        "itemIds": _dense_vector_struct(
            np.asarray(model.item_ids, dtype=np.float64)),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("userFactors", _matrix_arrow_type()),
            ("itemFactors", _matrix_arrow_type()),
            ("userIds", _vector_arrow_type()),
            ("itemIds", _vector_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("userFactors", "matrix"), ("itemFactors", "matrix"),
        ("userIds", "vector"), ("itemIds", "vector"),
    ])


def load_als_model(path: str):
    from spark_rapids_ml_tpu.models.als import ALSModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = ALSModel(
        user_factors=_dense_matrix_from_struct(row["userFactors"]),
        item_factors=_dense_matrix_from_struct(row["itemFactors"]),
        user_ids=_dense_vector_from_struct(row["userIds"]),
        item_ids=_dense_vector_from_struct(row["itemIds"]),
        uid=meta["uid"],
    )
    model.train_rmse_ = float(
        meta.get("extra", {}).get("trainRmse", float("nan")))
    return _restore_params(model, meta)


def save_json_state_model(model, path: str, state: Dict[str, Any],
                          overwrite: bool = False) -> None:
    """Generic small-state model writer: Spark metadata/params layout
    plus one JSON payload column — for models whose learned state is
    structured (category maps, selections, encoders) rather than
    matrix-shaped."""
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    _write_data_row(path, {"jsonState": json.dumps(state)})


def load_json_state_model(model_cls, path: str):
    """Counterpart of ``save_json_state_model``: returns (model with
    params restored, decoded state dict); the caller re-attaches its
    typed state fields."""
    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = model_cls(uid=meta["uid"])
    _restore_params(model, meta)
    return model, json.loads(row["jsonState"])


def save_fpgrowth_model(model, path: str, overwrite: bool = False) -> None:
    """FPGrowthModel: the mined (items, freq) pairs as one JSON payload
    column (items are JSON scalars — str/int/float — matching the
    practical domain of Spark's item type)."""
    if model.itemsets is None:
        raise ValueError("cannot save an unfitted FPGrowthModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"numBaskets": int(model.num_baskets)})
    payload = json.dumps([[list(s), int(c)] for s, c in model.itemsets])
    _write_data_row(path, {"itemsets": payload})


def load_fpgrowth_model(path: str):
    from spark_rapids_ml_tpu.models.fpm import FPGrowthModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    itemsets = [(tuple(s), int(c))
                for s, c in json.loads(row["itemsets"])]
    model = FPGrowthModel(
        itemsets=itemsets,
        num_baskets=int(meta.get("extra", {}).get("numBaskets", 0)),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_lsh_model(model, path: str, overwrite: bool = False) -> None:
    """LSH models: random-projection matrix + bucketLength (BRP) or the
    universal-hash coefficient pair (MinHash) — Spark persists the
    equivalent randUnitVectors / randCoefficients."""
    from spark_rapids_ml_tpu.models.lsh import (
        BucketedRandomProjectionLSHModel,
    )

    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    if isinstance(model, BucketedRandomProjectionLSHModel):
        if model.projections is None:
            raise ValueError("cannot save an unfitted LSH model")
        _write_metadata(
            path, cls, model.uid, model.param_map_for_metadata(),
            extra={"bucketLength": float(model.bucket_length)})
        row = {
            "projections": _dense_matrix_struct(model.projections),
            "coeffA": _dense_vector_struct(np.zeros(0)),
            "coeffB": _dense_vector_struct(np.zeros(0)),
        }
    else:
        if model.coeff_a is None:
            raise ValueError("cannot save an unfitted LSH model")
        _write_metadata(path, cls, model.uid,
                        model.param_map_for_metadata())
        row = {
            "projections": _dense_matrix_struct(np.zeros((0, 0))),
            "coeffA": _dense_vector_struct(
                np.asarray(model.coeff_a, dtype=np.float64)),
            "coeffB": _dense_vector_struct(
                np.asarray(model.coeff_b, dtype=np.float64)),
        }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("projections", _matrix_arrow_type()),
            ("coeffA", _vector_arrow_type()),
            ("coeffB", _vector_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("projections", "matrix"), ("coeffA", "vector"),
        ("coeffB", "vector"),
    ])


def load_lsh_model(path: str):
    import importlib

    meta = _read_metadata(path)
    row = _read_data_row(path)
    dotted = meta.get("pythonClass") or meta["class"]
    module_name, cls_name = dotted.rsplit(".", 1)
    model_cls = getattr(importlib.import_module(module_name), cls_name)
    coeff_a = _dense_vector_from_struct(row["coeffA"])
    if coeff_a.size:
        model = model_cls(
            coeff_a=coeff_a.astype(np.int64),
            coeff_b=_dense_vector_from_struct(
                row["coeffB"]).astype(np.int64),
            uid=meta["uid"])
    else:
        model = model_cls(
            projections=_dense_matrix_from_struct(row["projections"]),
            bucket_length=float(
                meta.get("extra", {}).get("bucketLength", 2.0)),
            uid=meta["uid"])
    return _restore_params(model, meta)


def save_word2vec_model(model, path: str, overwrite: bool = False) -> None:
    """Word2Vec layout: vocabulary array + (vocab, dim) vector matrix
    (Spark persists a wordVectors flat array + wordIndex map; one matrix
    plus the word list is the single-file equivalent)."""
    if model.vectors is None:
        raise ValueError("cannot save an unfitted Word2VecModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"numPairs": int(model.num_pairs_)})
    row = {
        "vocabulary": [str(t) for t in model.vocabulary],
        "vectors": _dense_matrix_struct(model.vectors),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("vocabulary", pa.list_(pa.string())),
            ("vectors", _matrix_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("vocabulary", "array<string>"), ("vectors", "matrix"),
    ])


def load_word2vec_model(path: str):
    from spark_rapids_ml_tpu.models.word2vec import Word2VecModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = Word2VecModel(
        vectors=_dense_matrix_from_struct(row["vectors"]),
        vocabulary=[str(t) for t in row["vocabulary"]],
        uid=meta["uid"],
    )
    model.num_pairs_ = int(meta.get("extra", {}).get("numPairs", 0))
    return _restore_params(model, meta)


def save_lda_model(model, path: str, overwrite: bool = False) -> None:
    """LDA layout: topic-word λ matrix + learned α vector (Spark's
    LocalLDAModel persists the same state: topicsMatrix +
    docConcentration)."""
    if model.topics is None:
        raise ValueError("cannot save an unfitted LDAModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"eta": float(model.eta), "numDocs": int(model.num_docs)})
    row = {
        "topics": _dense_matrix_struct(model.topics),
        "alpha": _dense_vector_struct(
            np.asarray(model.alpha, dtype=np.float64)),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("topics", _matrix_arrow_type()),
            ("alpha", _vector_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("topics", "matrix"), ("alpha", "vector"),
    ])


def load_lda_model(path: str):
    from spark_rapids_ml_tpu.models.lda import LDAModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    extras = meta.get("extra", {})
    model = LDAModel(
        topics=_dense_matrix_from_struct(row["topics"]),
        alpha=_dense_vector_from_struct(row["alpha"]),
        eta=float(extras.get("eta", 0.1)),
        num_docs=int(extras.get("numDocs", 0)),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_countvec_model(model, path: str, overwrite: bool = False) -> None:
    """Spark CountVectorizerModel layout: a vocabulary array row."""
    if model.vocabulary is None:
        raise ValueError("cannot save an unfitted CountVectorizerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    _write_data_row(
        path, {"vocabulary": [str(t) for t in model.vocabulary]},
        spark_fields=[("vocabulary", "array<string>")])


def load_countvec_model(path: str):
    from spark_rapids_ml_tpu.models.text import CountVectorizerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = CountVectorizerModel(
        vocabulary=[str(t) for t in row["vocabulary"]], uid=meta["uid"])
    return _restore_params(model, meta)


def save_idf_model(model, path: str, overwrite: bool = False) -> None:
    """Spark IDFModel layout: (idf vector, docFreq array, numDocs)."""
    if model.idf is None:
        raise ValueError("cannot save an unfitted IDFModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "idf": _dense_vector_struct(model.idf),
        "docFreq": [int(v) for v in np.asarray(model.doc_freq)],
        "numDocs": int(model.num_docs),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("idf", _vector_arrow_type()),
            ("docFreq", pa.list_(pa.int64())),
            ("numDocs", pa.int64()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("idf", "vector"), ("docFreq", "array<long>"), ("numDocs", "long"),
    ])


def load_idf_model(path: str):
    from spark_rapids_ml_tpu.models.text import IDFModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = IDFModel(
        idf=_dense_vector_from_struct(row["idf"]),
        doc_freq=np.asarray(list(row["docFreq"]), dtype=np.float64),
        num_docs=int(row["numDocs"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_aft_model(model, path: str, overwrite: bool = False) -> None:
    """Spark AFTSurvivalRegressionModel layout: (coefficients,
    intercept, scale)."""
    if model.coefficients is None:
        raise ValueError("cannot save an unfitted AFT model")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata(),
                    extra={"numIterations": int(model.num_iterations_),
                           "finalLoss": float(model.final_loss_)})
    row = {
        "coefficients": _dense_vector_struct(model.coefficients),
        "intercept": float(model.intercept),
        "scale": float(model.scale),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("coefficients", _vector_arrow_type()),
            ("intercept", pa.float64()),
            ("scale", pa.float64()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("coefficients", "vector"), ("intercept", "double"),
        ("scale", "double"),
    ])


def load_aft_model(path: str):
    from spark_rapids_ml_tpu.models.survival_regression import (
        AFTSurvivalRegressionModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = AFTSurvivalRegressionModel(
        coefficients=_dense_vector_from_struct(row["coefficients"]),
        intercept=float(row["intercept"]),
        scale=float(row["scale"]),
        uid=meta["uid"],
    )
    extras = meta.get("extra", {})
    model.num_iterations_ = int(extras.get("numIterations", 0))
    model.final_loss_ = float(extras.get("finalLoss", float("nan")))
    return _restore_params(model, meta)


def save_isotonic_model(model, path: str, overwrite: bool = False) -> None:
    """Spark IsotonicRegressionModelWriter layout: plain
    ``array<double>`` boundaries/predictions columns plus the isotonic
    boolean (NOT VectorUDT structs)."""
    if model.boundaries is None:
        raise ValueError("cannot save an unfitted IsotonicRegressionModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "boundaries": [float(v) for v in model.boundaries],
        "predictions": [float(v) for v in model.predictions],
        "isotonic": bool(model.get_or_default("isotonic")),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("boundaries", pa.list_(pa.float64())),
            ("predictions", pa.list_(pa.float64())),
            ("isotonic", pa.bool_()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("boundaries", "array<double>"), ("predictions", "array<double>"),
        ("isotonic", "boolean"),
    ])


def load_isotonic_model(path: str):
    from spark_rapids_ml_tpu.models.survival_regression import (
        IsotonicRegressionModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = IsotonicRegressionModel(
        boundaries=np.asarray(list(row["boundaries"]), dtype=np.float64),
        predictions=np.asarray(list(row["predictions"]), dtype=np.float64),
        uid=meta["uid"],
    )
    model = _restore_params(model, meta)
    if "isotonic" in row:
        model.set("isotonic", bool(row["isotonic"]))
    return model


def save_string_indexer_model(model, path: str,
                              overwrite: bool = False) -> None:
    """Spark StringIndexerModel layout: a data row carrying
    ``labelsArray`` (Spark 3.x stores one labels list per input column;
    we carry one)."""
    if model.labels is None:
        raise ValueError("cannot save an unfitted StringIndexerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    _write_data_row(
        path, {"labelsArray": [[str(v) for v in model.labels]]},
        spark_fields=[("labelsArray", "array<array<string>>")])


def load_string_indexer_model(path: str):
    from spark_rapids_ml_tpu.models.feature_transformers import (
        StringIndexerModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    # Spark 3.x writes labelsArray; Spark 2.x wrote labels
    labels = (list(row["labelsArray"][0]) if "labelsArray" in row
              else list(row["labels"]))
    model = StringIndexerModel(
        labels=[str(v) for v in labels], uid=meta["uid"])
    return _restore_params(model, meta)


def save_onehot_model(model, path: str, overwrite: bool = False) -> None:
    """Spark OneHotEncoderModel layout: a data row with categorySizes
    (one entry per input column; we carry one)."""
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    _write_data_row(path, {"categorySizes": [int(model.category_size)]},
                    spark_fields=[("categorySizes", "array<int>")])


def load_onehot_model(path: str):
    from spark_rapids_ml_tpu.models.feature_transformers import (
        OneHotEncoderModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = OneHotEncoderModel(
        category_size=int(list(row["categorySizes"])[0]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_selector_model(model, path: str, overwrite: bool = False) -> None:
    """Spark selector-model layout: a data row with selectedFeatures."""
    if model.selected_features is None:
        raise ValueError("cannot save an unfitted selector model")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(
        path, cls, model.uid, model.param_map_for_metadata(),
        extra={"selectorClass": type(model).__qualname__})
    _write_data_row(
        path,
        {"selectedFeatures": [int(i) for i in model.selected_features]},
        spark_fields=[("selectedFeatures", "array<int>")])


_SELECTOR_MODEL_CLASSES = ("ChiSqSelectorModel",
                           "VarianceThresholdSelectorModel",
                           "UnivariateFeatureSelectorModel")


def load_selector_model(path: str):
    from spark_rapids_ml_tpu.models import feature_transformers as ft
    from spark_rapids_ml_tpu.models import feature_transformers2 as ft2

    meta = _read_metadata(path)
    name = meta.get("extra", {}).get("selectorClass", "ChiSqSelectorModel")
    if name not in _SELECTOR_MODEL_CLASSES:
        raise ValueError(
            f"{path}: unknown selector model class {name!r} "
            f"(expected one of {_SELECTOR_MODEL_CLASSES})")
    row = _read_data_row(path)
    model_cls = getattr(ft, name, None) or getattr(ft2, name)
    model = model_cls(
        selected=[int(i) for i in row["selectedFeatures"]],
        uid=meta["uid"])
    return _restore_params(model, meta)


def save_mlp_model(model, path: str, overwrite: bool = False) -> None:
    """Spark MultilayerPerceptronClassificationModel layout: the layer
    sizes plus ONE flat weight vector (per layer: W row-major then b) —
    matching ``MultilayerPerceptronClassifierWriter`` upstream."""
    if model.weights_ is None:
        raise ValueError(
            "cannot save an unfitted MultilayerPerceptronModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    extras = {
        "numIterations": int(model.num_iterations_),
        "finalLoss": float(model.final_loss_),
        "layersFitted": [int(v) for v in model.layers_],
    }
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata(),
                    extra=extras)
    row = {"weights": _dense_vector_struct(model.flat_weights)}
    try:
        import pyarrow as pa

        schema = pa.schema([("weights", _vector_arrow_type())])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema,
                    spark_fields=[("weights", "vector")])


def load_mlp_model(path: str):
    from spark_rapids_ml_tpu.models.mlp import (
        MultilayerPerceptronModel,
        weights_from_flat,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    extras = meta.get("extra", {})
    # layersFitted is this writer's record; a genuine Spark-written
    # directory carries layers only in its paramMap — fall back there
    layers_raw = extras.get("layersFitted") \
        or meta.get("paramMap", {}).get("layers") \
        or meta.get("tpuParamMap", {}).get("layers")
    if layers_raw is None:
        raise ValueError(
            f"{path}: metadata carries no layer sizes (layersFitted / "
            "paramMap.layers)")
    layers = [int(v) for v in layers_raw]
    model = MultilayerPerceptronModel(
        layers=layers,
        weights=weights_from_flat(
            _dense_vector_from_struct(row["weights"]), layers),
        uid=meta["uid"],
    )
    model.num_iterations_ = int(extras.get("numIterations", 0))
    model.final_loss_ = float(extras.get("finalLoss", float("nan")))
    return _restore_params(model, meta)


def save_gmm_model(model, path: str, overwrite: bool = False) -> None:
    """GaussianMixtureModel layout: (weights vector, means matrix, covs
    stacked as a (k*d, d) matrix) — the covariance stack reshapes to
    (k, d, d) on load; Spark's writer stores gaussians row-per-component,
    an equivalent representation."""
    if model.weights is None:
        raise ValueError("cannot save an unfitted GaussianMixtureModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    extras = {
        "numIterations": int(model.num_iterations_),
        "logLikelihood": float(model.log_likelihood_),
    }
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata(),
                    extra=extras)
    k, d = model.means.shape
    row = {
        "weights": _dense_vector_struct(model.weights),
        "means": _dense_matrix_struct(model.means),
        "covs": _dense_matrix_struct(model.covs.reshape(k * d, d)),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("weights", _vector_arrow_type()),
                ("means", _matrix_arrow_type()),
                ("covs", _matrix_arrow_type()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("weights", "vector"), ("means", "matrix"), ("covs", "matrix"),
    ])


def load_gmm_model(path: str):
    from spark_rapids_ml_tpu.models.gaussian_mixture import (
        GaussianMixtureModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    means = _dense_matrix_from_struct(row["means"])
    k, d = means.shape
    model = GaussianMixtureModel(
        weights=_dense_vector_from_struct(row["weights"]),
        means=means,
        covs=_dense_matrix_from_struct(row["covs"]).reshape(k, d, d),
        uid=meta["uid"],
    )
    extras = meta.get("extra", {})
    model.num_iterations_ = int(extras.get("numIterations", 0))
    model.log_likelihood_ = float(extras.get("logLikelihood", float("nan")))
    return _restore_params(model, meta)


def save_bkm_model(model, path: str, overwrite: bool = False) -> None:
    """BisectingKMeansModel: the KMeansModel data layout (leaf centers
    matrix + training cost) — Spark persists its cluster tree, an
    implementation detail our flat-leaves design does not carry.
    Delegates to the KMeans writer so the wire format cannot drift."""
    if model.cluster_centers is None:
        raise ValueError("cannot save an unfitted BisectingKMeansModel")
    save_kmeans_model(model, path, overwrite=overwrite)


def load_bkm_model(path: str):
    from spark_rapids_ml_tpu.models.bisecting_kmeans import (
        BisectingKMeansModel,
    )

    return _load_centers_model(path, BisectingKMeansModel)


def _load_centers_model(path: str, model_cls):
    """(clusterCenters, trainingCost) layout shared by KMeansModel and
    BisectingKMeansModel."""
    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = model_cls(
        cluster_centers=_dense_matrix_from_struct(row["clusterCenters"]),
        uid=meta["uid"],
    )
    model.training_cost_ = row.get("trainingCost")
    return _restore_params(model, meta)


def load_kmeans_model(path: str):
    from spark_rapids_ml_tpu.models.kmeans import KMeansModel

    return _load_centers_model(path, KMeansModel)


def save_linreg_model(model, path: str, overwrite: bool = False) -> None:
    if model.coefficients is None:
        raise ValueError("cannot save an unfitted LinearRegressionModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "coefficients": _dense_vector_struct(model.coefficients),
        "intercept": float(model.intercept),
        "scale": 1.0,  # Spark writes (intercept, coefficients, scale)
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("coefficients", _vector_arrow_type()),
                ("intercept", pa.float64()),
                ("scale", pa.float64()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("coefficients", "vector"), ("intercept", "double"), ("scale", "double"),
    ])


def save_glm_model(model, path: str, overwrite: bool = False) -> None:
    """Spark GeneralizedLinearRegressionModel layout: (intercept,
    coefficients) — matching ``GeneralizedLinearRegressionModelWriter``
    upstream; fit summary scalars ride in the metadata extras."""
    if model.coefficients is None:
        raise ValueError(
            "cannot save an unfitted GeneralizedLinearRegressionModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    extras = {
        "numIterations": int(model.num_iterations_),
        "deviance": float(model.deviance_),
        "weightSum": float(model.weight_sum_),
    }
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata(),
                    extra=extras)
    row = {
        "intercept": float(model.intercept),
        "coefficients": _dense_vector_struct(model.coefficients),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("intercept", pa.float64()),
                ("coefficients", _vector_arrow_type()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("intercept", "double"), ("coefficients", "vector"),
    ])


def load_glm_model(path: str):
    from spark_rapids_ml_tpu.models.glm import (
        GeneralizedLinearRegressionModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = GeneralizedLinearRegressionModel(
        coefficients=_dense_vector_from_struct(row["coefficients"]),
        intercept=float(row["intercept"]),
        uid=meta["uid"],
    )
    extras = meta.get("extra", {})
    model.num_iterations_ = int(extras.get("numIterations", 0))
    model.deviance_ = float(extras.get("deviance", float("nan")))
    model.weight_sum_ = float(extras.get("weightSum", 0.0))
    return _restore_params(model, meta)


def save_svc_model(model, path: str, overwrite: bool = False) -> None:
    """Spark LinearSVCModel layout: (coefficients, intercept) — matching
    ``LinearSVCModel.LinearSVCModelWriter`` upstream."""
    if model.coefficients is None:
        raise ValueError("cannot save an unfitted LinearSVCModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "coefficients": _dense_vector_struct(model.coefficients),
        "intercept": float(model.intercept),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("coefficients", _vector_arrow_type()),
                ("intercept", pa.float64()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("coefficients", "vector"), ("intercept", "double"),
    ])


def load_svc_model(path: str):
    from spark_rapids_ml_tpu.models.linear_svc import LinearSVCModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = LinearSVCModel(
        coefficients=_dense_vector_from_struct(row["coefficients"]),
        intercept=float(row["intercept"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_logreg_model(model, path: str, overwrite: bool = False) -> None:
    multinomial = getattr(model, "coefficient_matrix", None) is not None
    if model.coefficients is None and not multinomial:
        raise ValueError("cannot save an unfitted LogisticRegressionModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    if multinomial:
        # Spark's multinomial layout: coefficientMatrix flattened row-major
        # into the vector slot + interceptVector/classes alongside
        k, d = model.coefficient_matrix.shape
        row = {
            "coefficients": _dense_vector_struct(
                model.coefficient_matrix.reshape(-1)
            ),
            "intercept": 0.0,
            "interceptVector": _dense_vector_struct(model.intercept_vector),
            "classes": _dense_vector_struct(model.classes_),
            "numClasses": int(k),
            "numFeatures": int(d),
        }
        try:
            import pyarrow as pa

            schema = pa.schema(
                [
                    ("coefficients", _vector_arrow_type()),
                    ("intercept", pa.float64()),
                    ("interceptVector", _vector_arrow_type()),
                    ("classes", _vector_arrow_type()),
                    ("numClasses", pa.int32()),
                    ("numFeatures", pa.int32()),
                ]
            )
        except ImportError:  # pragma: no cover
            schema = None
        _write_data_row(path, row, schema=schema, spark_fields=[
            ("coefficients", "vector"), ("intercept", "double"),
            ("interceptVector", "vector"), ("classes", "vector"),
            ("numClasses", "integer"), ("numFeatures", "integer"),
        ])
        return
    row = {
        "coefficients": _dense_vector_struct(model.coefficients),
        "intercept": float(model.intercept),
        "numClasses": 2,
        "numFeatures": int(np.asarray(model.coefficients).shape[0]),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("coefficients", _vector_arrow_type()),
                ("intercept", pa.float64()),
                ("numClasses", pa.int32()),
                ("numFeatures", pa.int32()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("coefficients", "vector"), ("intercept", "double"),
        ("numClasses", "integer"), ("numFeatures", "integer"),
    ])


def load_logreg_model(path: str):
    from spark_rapids_ml_tpu.models.logistic_regression import (
        LogisticRegressionModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    n_classes = int(row.get("numClasses", 2))
    if n_classes > 2 and row.get("interceptVector") is not None:
        d = int(row["numFeatures"])
        model = LogisticRegressionModel(
            coefficient_matrix=_dense_vector_from_struct(
                row["coefficients"]
            ).reshape(n_classes, d),
            intercept_vector=_dense_vector_from_struct(row["interceptVector"]),
            classes=_dense_vector_from_struct(row["classes"]),
            uid=meta["uid"],
        )
        return _restore_params(model, meta)
    model = LogisticRegressionModel(
        coefficients=_dense_vector_from_struct(row["coefficients"]),
        intercept=float(row["intercept"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def load_linreg_model(path: str):
    from spark_rapids_ml_tpu.models.linear_regression import LinearRegressionModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = LinearRegressionModel(
        coefficients=_dense_vector_from_struct(row["coefficients"]),
        intercept=float(row["intercept"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def load_pca_model(path: str):
    from spark_rapids_ml_tpu.models.pca import PCAModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = PCAModel(
        pc=_dense_matrix_from_struct(row["pc"]),
        explained_variance=_dense_vector_from_struct(row["explainedVariance"]),
        mean=_dense_vector_from_struct(row["mean"]) if "mean" in row else None,
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_svd_model(model, path: str, overwrite: bool = False) -> None:
    if model.components is None:
        raise ValueError("cannot save an unfitted TruncatedSVDModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "V": _dense_matrix_struct(model.components),
        "s": _dense_vector_struct(model.singular_values),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [("V", _matrix_arrow_type()), ("s", _vector_arrow_type())]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("V", "matrix"), ("s", "vector"),
    ])


def load_svd_model(path: str):
    from spark_rapids_ml_tpu.models.svd import TruncatedSVDModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = TruncatedSVDModel(
        components=_dense_matrix_from_struct(row["V"]),
        singular_values=_dense_vector_from_struct(row["s"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_scaler_model(model, path: str, overwrite: bool = False) -> None:
    if model.mean is None:
        raise ValueError("cannot save an unfitted StandardScalerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "mean": _dense_vector_struct(model.mean),
        "std": _dense_vector_struct(model.std),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [("mean", _vector_arrow_type()), ("std", _vector_arrow_type())]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("mean", "vector"), ("std", "vector"),
    ])


def load_scaler_model(path: str):
    from spark_rapids_ml_tpu.models.scaler import StandardScalerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = StandardScalerModel(
        mean=_dense_vector_from_struct(row["mean"]),
        std=_dense_vector_from_struct(row["std"]),
        uid=meta["uid"],
    )
    return _restore_params(model, meta)


def save_knn_model(model, path: str, overwrite: bool = False) -> None:
    """NearestNeighborsModel: the fitted item matrix is the model payload
    (brute-force KNN has no reduced parameters), stored in the same
    DenseMatrix wire struct every other model uses."""
    if model.items is None:
        raise ValueError("cannot save an unfitted NearestNeighborsModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {"items": _dense_matrix_struct(model.items)}
    try:
        import pyarrow as pa

        schema = pa.schema([("items", _matrix_arrow_type())])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[("items", "matrix")])


def load_knn_model(path: str):
    from spark_rapids_ml_tpu.models.nearest_neighbors import (
        NearestNeighborsModel,
    )

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = NearestNeighborsModel(
        items=_dense_matrix_from_struct(row["items"])
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_forest_model(model, path: str, overwrite: bool = False) -> None:
    """RandomForest models: the ensemble's (feature, threshold, leafValue)
    arrays plus bin edges — all as DenseMatrix wire structs (int arrays
    stored as exact small-valued doubles, cast back on load). A 3-D
    classification leaf tensor flattens to (trees, leaves*classes) with
    ``numClasses``/``classes`` alongside."""
    if model.ensemble_ is None:
        raise ValueError("cannot save an unfitted RandomForest model")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    leaf = np.asarray(model.ensemble_.leaf_value, dtype=np.float64)
    if leaf.ndim == 3:
        n_classes = leaf.shape[2]
        leaf2d = leaf.reshape(leaf.shape[0], -1)
        classes = np.asarray(model.classes_, dtype=np.float64)
    else:
        n_classes = 0
        leaf2d = leaf
        classes = np.zeros((0,), dtype=np.float64)
    row = {
        "feature": _dense_matrix_struct(
            np.asarray(model.ensemble_.feature, dtype=np.float64)
        ),
        "threshold": _dense_matrix_struct(
            np.asarray(model.ensemble_.threshold, dtype=np.float64)
        ),
        "leafValue": _dense_matrix_struct(leaf2d),
        "edges": _dense_matrix_struct(
            np.asarray(model.edges_, dtype=np.float64)
        ),
        "classes": _dense_vector_struct(classes),
        "numClasses": int(n_classes),
        "featureImportances": _dense_vector_struct(
            np.asarray(
                model.feature_importances_
                if model.feature_importances_ is not None else [],
                dtype=np.float64,
            )
        ),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("feature", _matrix_arrow_type()),
                ("threshold", _matrix_arrow_type()),
                ("leafValue", _matrix_arrow_type()),
                ("edges", _matrix_arrow_type()),
                ("classes", _vector_arrow_type()),
                ("numClasses", pa.int64()),
                ("featureImportances", _vector_arrow_type()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("feature", "matrix"), ("threshold", "matrix"),
        ("leafValue", "matrix"), ("edges", "matrix"),
        ("classes", "vector"), ("numClasses", "long"),
        ("featureImportances", "vector"),
    ])


def load_forest_model(path: str):
    import importlib

    from spark_rapids_ml_tpu.ops.forest_kernel import TreeEnsemble

    meta = _read_metadata(path)
    row = _read_data_row(path)
    dotted = meta.get("pythonClass") or meta["class"]
    module_name, cls_name = dotted.rsplit(".", 1)
    model_cls = getattr(importlib.import_module(module_name), cls_name)
    feature = _dense_matrix_from_struct(row["feature"]).astype(np.int32)
    threshold = _dense_matrix_from_struct(row["threshold"]).astype(np.int32)
    leaf2d = _dense_matrix_from_struct(row["leafValue"])
    n_classes = int(row["numClasses"])
    classes = _dense_vector_from_struct(row["classes"])
    if n_classes:
        leaf = leaf2d.reshape(leaf2d.shape[0], -1, n_classes)
    else:
        leaf = leaf2d
        classes = None
    model = model_cls(
        ensemble=TreeEnsemble(
            feature=feature, threshold=threshold, leaf_value=leaf
        ),
        edges=_dense_matrix_from_struct(row["edges"]),
        classes=classes,
    )
    fi = _dense_vector_from_struct(
        row.get("featureImportances", {"values": []})
    )
    model.feature_importances_ = fi if fi.size else None
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_gbt_model(model, path: str, overwrite: bool = False) -> None:
    """GBT models: the boosted TreeEnsemble plus the additive-model scalars
    (init, stepSize) — same DenseMatrix wire structs as the forest."""
    if model.ensemble_ is None:
        raise ValueError("cannot save an unfitted GBT model")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "feature": _dense_matrix_struct(
            np.asarray(model.ensemble_.feature, dtype=np.float64)
        ),
        "threshold": _dense_matrix_struct(
            np.asarray(model.ensemble_.threshold, dtype=np.float64)
        ),
        "leafValue": _dense_matrix_struct(
            np.asarray(model.ensemble_.leaf_value, dtype=np.float64)
        ),
        "edges": _dense_matrix_struct(
            np.asarray(model.edges_, dtype=np.float64)
        ),
        "init": float(model.init_),
        "stepSize": float(model.step_size_),
        "featureImportances": _dense_vector_struct(
            np.asarray(
                model.feature_importances_
                if model.feature_importances_ is not None else [],
                dtype=np.float64,
            )
        ),
    }
    try:
        import pyarrow as pa

        schema = pa.schema(
            [
                ("feature", _matrix_arrow_type()),
                ("threshold", _matrix_arrow_type()),
                ("leafValue", _matrix_arrow_type()),
                ("edges", _matrix_arrow_type()),
                ("init", pa.float64()),
                ("stepSize", pa.float64()),
                ("featureImportances", _vector_arrow_type()),
            ]
        )
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("feature", "matrix"), ("threshold", "matrix"),
        ("leafValue", "matrix"), ("edges", "matrix"),
        ("init", "double"), ("stepSize", "double"),
        ("featureImportances", "vector"),
    ])


def load_gbt_model(path: str):
    import importlib

    from spark_rapids_ml_tpu.ops.forest_kernel import TreeEnsemble

    meta = _read_metadata(path)
    row = _read_data_row(path)
    dotted = meta.get("pythonClass") or meta["class"]
    module_name, cls_name = dotted.rsplit(".", 1)
    model_cls = getattr(importlib.import_module(module_name), cls_name)
    model = model_cls(
        ensemble=TreeEnsemble(
            feature=_dense_matrix_from_struct(row["feature"]).astype(np.int32),
            threshold=_dense_matrix_from_struct(row["threshold"]).astype(
                np.int32
            ),
            leaf_value=_dense_matrix_from_struct(row["leafValue"]),
        ),
        edges=_dense_matrix_from_struct(row["edges"]),
        init=float(row["init"]),
        step_size=float(row["stepSize"]),
    )
    fi = _dense_vector_from_struct(
        row.get("featureImportances", {"values": []})
    )
    model.feature_importances_ = fi if fi.size else None
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_minmax_model(model, path: str, overwrite: bool = False) -> None:
    if model.original_min is None:
        raise ValueError("cannot save an unfitted MinMaxScalerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "originalMin": _dense_vector_struct(model.original_min),
        "originalMax": _dense_vector_struct(model.original_max),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("originalMin", _vector_arrow_type()),
            ("originalMax", _vector_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("originalMin", "vector"), ("originalMax", "vector"),
    ])


def load_minmax_model(path: str):
    from spark_rapids_ml_tpu.models.feature_scalers import MinMaxScalerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = MinMaxScalerModel(
        original_min=_dense_vector_from_struct(row["originalMin"]),
        original_max=_dense_vector_from_struct(row["originalMax"]),
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_maxabs_model(model, path: str, overwrite: bool = False) -> None:
    if model.max_abs is None:
        raise ValueError("cannot save an unfitted MaxAbsScalerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {"maxAbs": _dense_vector_struct(model.max_abs)}
    try:
        import pyarrow as pa

        schema = pa.schema([("maxAbs", _vector_arrow_type())])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema,
                    spark_fields=[("maxAbs", "vector")])


def load_maxabs_model(path: str):
    from spark_rapids_ml_tpu.models.feature_scalers import MaxAbsScalerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = MaxAbsScalerModel(
        max_abs=_dense_vector_from_struct(row["maxAbs"])
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_nb_model(model, path: str, overwrite: bool = False) -> None:
    """NaiveBayesModel: pi / theta (+ sigma for gaussian) / classes."""
    if model.theta is None:
        raise ValueError("cannot save an unfitted NaiveBayesModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    has_sigma = model.sigma is not None
    row = {
        "pi": _dense_vector_struct(model.pi),
        "theta": _dense_matrix_struct(model.theta),
        "sigma": _dense_matrix_struct(
            model.sigma if has_sigma else np.zeros((0, 0))
        ),
        "classes": _dense_vector_struct(np.asarray(model.classes_, float)),
        "hasSigma": bool(has_sigma),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("pi", _vector_arrow_type()),
            ("theta", _matrix_arrow_type()),
            ("sigma", _matrix_arrow_type()),
            ("classes", _vector_arrow_type()),
            ("hasSigma", pa.bool_()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema, spark_fields=[
        ("pi", "vector"), ("theta", "matrix"), ("sigma", "matrix"),
        ("classes", "vector"), ("hasSigma", "boolean"),
    ])


def load_nb_model(path: str):
    from spark_rapids_ml_tpu.models.naive_bayes import NaiveBayesModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    sigma = (
        _dense_matrix_from_struct(row["sigma"]) if row["hasSigma"] else None
    )
    model = NaiveBayesModel(
        pi=_dense_vector_from_struct(row["pi"]),
        theta=_dense_matrix_from_struct(row["theta"]),
        sigma=sigma,
        classes=_dense_vector_from_struct(row["classes"]),
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_robust_model(model, path: str, overwrite: bool = False) -> None:
    if model.median is None:
        raise ValueError("cannot save an unfitted RobustScalerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {
        "median": _dense_vector_struct(model.median),
        "range": _dense_vector_struct(model.qrange),
    }
    try:
        import pyarrow as pa

        schema = pa.schema([
            ("median", _vector_arrow_type()),
            ("range", _vector_arrow_type()),
        ])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema,
                    spark_fields=[("median", "vector"),
                                  ("range", "vector")])


def load_robust_model(path: str):
    from spark_rapids_ml_tpu.models.feature_scalers import RobustScalerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = RobustScalerModel(
        median=_dense_vector_from_struct(row["median"]),
        qrange=_dense_vector_from_struct(row["range"]),
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


def save_imputer_model(model, path: str, overwrite: bool = False) -> None:
    if model.surrogates is None:
        raise ValueError("cannot save an unfitted ImputerModel")
    _require_target(path, overwrite)
    cls = f"{type(model).__module__}.{type(model).__qualname__}"
    _write_metadata(path, cls, model.uid, model.param_map_for_metadata())
    row = {"surrogates": _dense_vector_struct(model.surrogates)}
    try:
        import pyarrow as pa

        schema = pa.schema([("surrogates", _vector_arrow_type())])
    except ImportError:  # pragma: no cover
        schema = None
    _write_data_row(path, row, schema=schema,
                    spark_fields=[("surrogates", "vector")])


def load_imputer_model(path: str):
    from spark_rapids_ml_tpu.models.imputer import ImputerModel

    meta = _read_metadata(path)
    row = _read_data_row(path)
    model = ImputerModel(
        surrogates=_dense_vector_from_struct(row["surrogates"])
    )
    model.uid = meta["uid"]
    return _restore_params(model, meta)


# -- generic load + atomic save layer --------------------------------------


def load_model(path: str):
    """Load any saved model/estimator by its metadata ``pythonClass``.

    The serving registry's load-from-disk entry point: reads the Spark
    metadata line, imports the recorded Python class, and delegates to its
    ``load`` staticmethod — so one call handles every model family this
    module can write, including ones added later.
    """
    meta = _read_metadata(path)
    dotted = meta.get("pythonClass")
    if not dotted:
        raise ValueError(
            f"{path}: metadata carries no 'pythonClass' (a Spark-written "
            "directory?); load it with the class-specific reader instead"
        )
    module_name, cls_name = dotted.rsplit(".", 1)
    cls = getattr(importlib.import_module(module_name), cls_name)
    loader = getattr(cls, "load", None)
    if loader is None:
        raise ValueError(f"{dotted} has no load() entry point")
    return loader(path)


def _atomic_save(save_fn):
    """Make a ``save_*`` writer atomic: the payload is written to a temp
    sibling directory, then ``os.replace``d into place — the same
    tmp+rename pattern the flight recorder uses for dumps. A save that
    crashes mid-write leaves the target untouched (either the previous
    model or nothing), never a half-written directory for the registry's
    load path to pick up.
    """

    @functools.wraps(save_fn)
    def wrapper(obj, path, *args, overwrite: bool = False, **kwargs):
        if os.path.exists(path) and not overwrite:
            _require_target(path, False)  # the standard FileExistsError
        token = f"{os.getpid()}-{uuid.uuid4().hex[:8]}"
        tmp = f"{path}.tmp-{token}"
        old = f"{path}.old-{token}"
        try:
            save_fn(obj, tmp, *args, overwrite=True, **kwargs)
            # Swap via rename-aside: both steps are atomic renames, so a
            # crash at any point leaves either the previous model at
            # ``path`` or the complete previous model at the ``.old``
            # sibling — never a half-written directory, and never both
            # copies gone (an rmtree-then-replace swap would have a
            # lose-both window as wide as the rmtree).
            if os.path.exists(path):  # validated overwrite=True above
                os.replace(path, old)
            os.replace(tmp, path)
            shutil.rmtree(old, ignore_errors=True)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    wrapper.__wrapped_save__ = save_fn
    return wrapper


# Wrap every writer in this module (including ones future sections add
# above this line). Delegating writers (save_bkm_model → save_kmeans_model)
# stage twice, which is harmless; the outer replace is the one that counts.
for _name, _fn in list(globals().items()):
    if _name.startswith("save_") and callable(_fn):
        globals()[_name] = _atomic_save(_fn)
del _name, _fn
