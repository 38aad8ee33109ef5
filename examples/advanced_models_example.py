"""The beyond-parity model families: NearestNeighbors, DBSCAN, UMAP,
RandomForest, OneVsRest, plus model selection with CrossValidator.

These cover the algorithms the reference project's later generations ship
(cuML-backed there), rebuilt TPU-native — pairwise-distance MXU kernels,
label propagation, dense-force embedding optimization, histogram trees.

Run:  python examples/advanced_models_example.py
(CPU works; a TPU is used automatically when visible.)
"""

import numpy as np

from spark_rapids_ml_tpu import (
    DBSCAN,
    CrossValidator,
    LinearRegression,
    LogisticRegression,
    NearestNeighbors,
    OneVsRest,
    ParamGridBuilder,
    RandomForestRegressor,
    RegressionEvaluator,
    UMAP,
)
from spark_rapids_ml_tpu.data.frame import VectorFrame

rng = np.random.default_rng(0)

# --- exact brute-force KNN ------------------------------------------------
items = rng.normal(size=(2000, 32)).astype(np.float32)
knn = NearestNeighbors().setK(5).fit(items)
dist, idx = knn.kneighbors(items[:3])
print("knn: first query's neighbors", idx[0], "at distances", np.round(dist[0], 3))

# --- DBSCAN ---------------------------------------------------------------
blobs = np.concatenate(
    [rng.normal(loc=c, scale=0.4, size=(100, 2)) for c in ((0, 0), (6, 6))]
)
db = DBSCAN().setEps(1.0).setMinPts(5).fit(blobs)
print("dbscan: clusters =", db.n_clusters_, "noise =", int((db.labels_ == -1).sum()))

# --- UMAP -----------------------------------------------------------------
um = UMAP().setNNeighbors(10).setNEpochs(100).fit(blobs)
print("umap: embedding shape", um.embedding_.shape)

# --- RandomForest ---------------------------------------------------------
x = rng.uniform(-2, 2, size=(1000, 5))
y = np.sin(2 * x[:, 0]) + (x[:, 1] > 0) * 2.0
frame = VectorFrame({"features": x, "label": y})
rf = RandomForestRegressor().setNumTrees(25).setMaxDepth(6).fit(frame)
pred = np.asarray(rf.transform(frame).column("prediction"))
print("forest: R² =", round(1 - ((y - pred) ** 2).sum() / ((y - y.mean()) ** 2).sum(), 3))

# --- OneVsRest multiclass -------------------------------------------------
xc = np.concatenate([rng.normal(loc=c, size=(80, 3)) for c in (0.0, 3.0, 6.0)])
yc = np.repeat([0.0, 1.0, 2.0], 80)
ovr = OneVsRest(classifier=LogisticRegression().setMaxIter(20)).fit(
    VectorFrame({"features": xc, "label": yc})
)
acc = (np.asarray(ovr.transform(VectorFrame({"features": xc})).column("prediction")) == yc).mean()
print("one-vs-rest: accuracy", round(float(acc), 3))

# --- CrossValidator model selection --------------------------------------
cv = CrossValidator(
    estimator=LinearRegression(),
    estimatorParamMaps=ParamGridBuilder().addGrid("regParam", [1e-6, 1e2]).build(),
    evaluator=RegressionEvaluator(),
    numFolds=3,
)
best = cv.fit(VectorFrame({"features": x, "label": y}))
print("cross-validation: avg rmse per grid point", [round(m, 4) for m in best.avgMetrics])


def feature_transformers_example():
    """Round-4 additions: Imputer, RobustScaler, Binarizer."""
    import numpy as np

    from spark_rapids_ml_tpu import Binarizer, Imputer, RobustScaler
    from spark_rapids_ml_tpu.data.frame import as_vector_frame

    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 4)) * np.array([1.0, 10.0, 0.1, 3.0])
    x[::13, 1] = np.nan
    frame = as_vector_frame(x, "features")

    imp = Imputer().setStrategy("median").fit(frame)
    filled = imp.transform(frame)
    print("Imputer surrogates:", np.round(imp.surrogates, 3).tolist())

    rs = (
        RobustScaler().setInputCol("imputed_features")
        .setWithCentering(True).fit(filled)
    )
    print("RobustScaler median:", np.round(rs.median, 3).tolist())

    b = Binarizer().setThreshold(0.0).transform(frame)
    print("Binarizer ones fraction:",
          round(float(np.mean(np.stack(
              list(b.column("binarized_features"))
          ))), 3))


if __name__ == "__main__":  # pragma: no cover
    feature_transformers_example()
