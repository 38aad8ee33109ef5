"""Out-of-core fits: generators stream through the device with bounded memory.

Every estimator accepts a chunk generator (one-shot, single-pass algorithms)
or a zero-arg factory returning a fresh iterator (multi-pass algorithms:
KMeans Lloyd, LogisticRegression Newton). The dataset never materializes —
the analogue of the reference's per-partition streaming
(``RapidsRowMatrix.scala:168-202``), consumer-facing.

Run:  python examples/out_of_core_example.py
"""

import numpy as np

from spark_rapids_ml_tpu import KMeans, LinearRegression, LogisticRegression, PCA

N_ROWS, N_FEATS, CHUNK = 500_000, 64, 50_000


def chunks():
    rng = np.random.default_rng(7)
    for _ in range(N_ROWS // CHUNK):
        yield rng.normal(size=(CHUNK, N_FEATS)).astype(np.float32)


# -- PCA: re-iterable factory → exact two-pass centering -------------------
model = PCA().setK(8).fit(chunks)
print("pca components:", model.pc.shape, "timings:", model.fit_timings_)


# -- LinearRegression / LogisticRegression: (X, y) chunk pairs -------------
def xy_chunks():
    rng = np.random.default_rng(8)
    w = np.linspace(-1, 1, N_FEATS)
    for _ in range(20):
        x = rng.normal(size=(20_000, N_FEATS))
        yield x, x @ w + 0.5 + 0.01 * rng.normal(size=20_000)


lin = LinearRegression().setRegParam(0.01).fit(xy_chunks)
print("linreg intercept:", round(lin.intercept, 3))


def cls_chunks():
    rng = np.random.default_rng(9)
    w = np.linspace(-1, 1, N_FEATS)
    for _ in range(20):
        x = rng.normal(size=(20_000, N_FEATS))
        yield x, (rng.random(20_000) < 1 / (1 + np.exp(-(x @ w)))).astype(float)


log = LogisticRegression().setRegParam(0.01).fit(cls_chunks)
print("logreg n_iter:", log.n_iter_)

# -- KMeans: multi-pass Lloyd over the stream ------------------------------
km = KMeans().setK(4).fit(chunks)
print("kmeans cost:", round(km.training_cost_, 1))

# An IN-MEMORY input needs no API change either: PCA.fit walks a matrix in
# the same batches whatever its size; LinearRegression and KMeans stream one
# once it exceeds TPUML_STREAM_THRESHOLD_BYTES (default 1 GiB).
