"""Out-of-core fit inside the Estimators: streamed-vs-oneshot oracles.

The reference never materializes the dataset in one buffer — it streams
partition chunks (``RapidsRowMatrix.scala:168-202``). These tests pin the
user-facing analogue: ``fit()`` accepts generators / chunk factories and
silently streams oversized in-memory inputs, with results matching the
one-shot path to oracle tolerance.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu import KMeans, LinearRegression, PCA
from spark_rapids_ml_tpu.data.batches import BatchSource


@pytest.fixture
def data(rng):
    return rng.normal(size=(3000, 24)) * np.linspace(0.5, 3, 24) + 2.0


# -- BatchSource mechanics -------------------------------------------------

def test_batch_source_rebatches_uneven_chunks(rng):
    chunks = [rng.normal(size=(m, 7)) for m in (13, 200, 1, 64, 30)]
    src = BatchSource(lambda: iter(chunks), batch_rows=50)
    total = 0
    batches = list(src.batches())
    for i, (batch, mask) in enumerate(batches):
        assert batch.shape == (50, 7)
        valid = 50 if mask is None else int(mask.sum())
        if i < len(batches) - 1:
            assert mask is None
        total += valid
    assert total == 13 + 200 + 1 + 64 + 30
    # re-iterable: identical content on a second pass
    again = list(src.batches())
    np.testing.assert_array_equal(batches[0][0], again[0][0])


def test_batch_source_oneshot_single_pass(rng):
    it = iter([rng.normal(size=(10, 4))])
    src = BatchSource(it, batch_rows=8)
    assert not src.reiterable
    assert src.n_features == 4
    list(src.batches())
    with pytest.raises(RuntimeError, match="already consumed"):
        list(src.batches())


def test_batch_source_detects_shared_underlying_iterator(rng):
    """A factory the identity check can't see through (fresh map object over
    one shared generator) must raise, not silently zero pass 2."""
    shared = (rng.normal(size=(20, 4)) for _ in range(5))
    src = BatchSource(lambda: map(np.asarray, shared), batch_rows=16)
    assert src.reiterable  # looks re-iterable...
    list(src.batches())
    with pytest.raises(RuntimeError, match="FRESH iterator"):
        list(src.batches())


def test_linreg_fake_factory_demoted_not_truncated(rng):
    """`lambda: gen` over one (X, y) generator: the one-shot demotion must
    still fire through the chunk transform, fitting on ALL the data."""
    x = rng.normal(size=(900, 5))
    y = x @ np.arange(1.0, 6.0) + 0.25
    gen = ((x[i:i + 100], y[i:i + 100]) for i in range(0, 900, 100))
    streamed = LinearRegression().fit(lambda: gen)
    oneshot = LinearRegression().fit(x, y)
    np.testing.assert_allclose(
        streamed.coefficients, oneshot.coefficients, atol=5e-4
    )


def test_batch_source_empty_raises():
    with pytest.raises(ValueError, match="empty"):
        BatchSource(iter([]))


def test_batch_source_demotes_fake_factory(rng):
    """`lambda: gen` over one generator object is one-shot, not re-iterable."""
    gen = (rng.normal(size=(10, 3)) for _ in range(3))
    src = BatchSource(lambda: gen, batch_rows=16)
    assert not src.reiterable
    assert sum(
        b.shape[0] if m is None else int(m.sum()) for b, m in src.batches()
    ) == 30


# -- PCA -------------------------------------------------------------------

def test_pca_streamed_generator_matches_oneshot(data):
    oneshot = PCA().setK(4).fit(data)

    def chunks():
        for i in range(0, data.shape[0], 177):
            yield data[i:i + 177]

    streamed = PCA().setK(4).setBatchRows(256).fit(chunks)
    np.testing.assert_allclose(
        np.abs(streamed.pc), np.abs(oneshot.pc), atol=2e-4
    )
    np.testing.assert_allclose(streamed.mean, oneshot.mean, atol=1e-4)
    np.testing.assert_allclose(
        streamed.explained_variance, oneshot.explained_variance, rtol=1e-3
    )


def test_pca_streamed_oneshot_iterator(data):
    """A plain generator (not re-iterable) takes the one-pass stats path."""
    oneshot = PCA().setK(3).fit(data)
    gen = (data[i:i + 500] for i in range(0, data.shape[0], 500))
    streamed = PCA().setK(3).setBatchRows(512).fit(gen)
    np.testing.assert_allclose(
        np.abs(streamed.pc), np.abs(oneshot.pc), atol=2e-3
    )


def test_pca_matrix_in_several_batches_equals_one_batch(data):
    """No size threshold: a matrix is a stream whatever its size, several
    views and a masked tail under ``batchRows``, one batch of exactly its
    rows otherwise."""
    several = PCA().setK(4).setBatchRows(256).fit(data)
    one = PCA().setK(4).fit(data)
    assert several.fit_report_.extra["ingest"]["batches"] > 2
    # one batch is its own mean: the Gram summed about it in pass 1 stands
    # (``tests/test_streaming_shift.py``) and the rows cross once
    ingest = one.fit_report_.extra["ingest"]
    assert ingest["batches"] == ingest["passes"] == 1
    assert ingest["gram_shift"] == {"accepted": True, "ratio": 0.0}
    assert ingest["rows_put"] == len(data)
    np.testing.assert_allclose(
        np.abs(several.pc), np.abs(one.pc), atol=2e-4
    )


@pytest.mark.parametrize("use_xla_dot,use_xla_svd", [
    (True, False), (False, True), (False, False),
])
def test_pca_streamed_path_combos(data, use_xla_dot, use_xla_svd):
    oneshot = (
        PCA().setK(3).setUseXlaDot(use_xla_dot).setUseXlaSvd(use_xla_svd)
        .fit(data)
    )
    streamed = (
        PCA().setK(3).setUseXlaDot(use_xla_dot).setUseXlaSvd(use_xla_svd)
        .setBatchRows(512)
        .fit(lambda: (data[i:i + 400] for i in range(0, len(data), 400)))
    )
    np.testing.assert_allclose(
        np.abs(streamed.pc), np.abs(oneshot.pc), atol=2e-4
    )


def test_pca_streamed_k_validation(data):
    with pytest.raises(ValueError, match="at most the number of features"):
        PCA().setK(99).fit(lambda: iter([data]))


# -- LinearRegression ------------------------------------------------------

def test_linreg_streamed_matches_oneshot(rng):
    x = rng.normal(size=(4000, 12))
    w = rng.normal(size=12)
    y = x @ w + 1.5 + 0.01 * rng.normal(size=4000)
    oneshot = LinearRegression().setRegParam(0.1).fit(x, y)

    def chunks():
        for i in range(0, 4000, 333):
            yield (x[i:i + 333], y[i:i + 333])

    streamed = LinearRegression().setRegParam(0.1).fit(chunks)
    np.testing.assert_allclose(
        streamed.coefficients, oneshot.coefficients, atol=5e-4
    )
    assert abs(streamed.intercept - oneshot.intercept) < 5e-4


def test_linreg_size_threshold_triggers_streaming(rng, monkeypatch):
    x = rng.normal(size=(500, 6))
    y = x @ np.arange(1.0, 7.0) - 0.5
    monkeypatch.setenv("TPUML_STREAM_THRESHOLD_BYTES", "1024")
    streamed = LinearRegression().fit(x, y)
    monkeypatch.setenv("TPUML_STREAM_THRESHOLD_BYTES", str(1 << 40))
    oneshot = LinearRegression().fit(x, y)
    np.testing.assert_allclose(
        streamed.coefficients, oneshot.coefficients, atol=1e-4
    )


def test_linreg_streamed_host_path(rng):
    x = rng.normal(size=(2000, 5))
    y = x @ np.arange(1.0, 6.0) + 2.0
    oneshot = LinearRegression().setUseXlaDot(False).fit(x, y)
    streamed = LinearRegression().setUseXlaDot(False).fit(
        lambda: ((x[i:i + 300], y[i:i + 300]) for i in range(0, 2000, 300))
    )
    np.testing.assert_allclose(
        streamed.coefficients, oneshot.coefficients, atol=1e-8
    )


def test_linreg_streamed_int_features_float_labels(rng):
    """Integer X chunks must not truncate float labels."""
    x = rng.integers(0, 5, size=(1000, 4)).astype(np.int64)
    w = np.array([0.25, -0.5, 1.75, 0.1])
    y = x @ w + 0.7
    streamed = LinearRegression().fit(
        lambda: ((x[i:i + 200], y[i:i + 200]) for i in range(0, 1000, 200))
    )
    np.testing.assert_allclose(streamed.coefficients, w, atol=1e-4)
    assert abs(streamed.intercept - 0.7) < 1e-3


def test_linreg_streamed_bad_chunk_shape(rng):
    x = rng.normal(size=(10, 3))
    with pytest.raises(ValueError, match=r"\(X, y\) tuples"):
        LinearRegression().fit(lambda: iter([x]))


# -- KMeans ----------------------------------------------------------------

def test_kmeans_streamed_recovers_clusters(rng):
    true_centers = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [10.0, 10.0]])
    x = np.concatenate([
        c + 0.3 * rng.normal(size=(500, 2)) for c in true_centers
    ])
    rng.shuffle(x)

    def chunks():
        for i in range(0, len(x), 173):
            yield x[i:i + 173]

    model = KMeans().setK(4).setSeed(7).fit(chunks)
    oneshot = KMeans().setK(4).setSeed(7).fit(x)
    # same data, same structure: streamed cost within a few % of one-shot
    streamed_cost = model.compute_cost(x)
    oneshot_cost = oneshot.compute_cost(x)
    assert streamed_cost <= oneshot_cost * 1.05
    # each true center has a found center nearby
    found = np.asarray(model.cluster_centers)
    for c in true_centers:
        assert np.min(np.linalg.norm(found - c, axis=1)) < 0.5


def test_kmeans_streamed_host_path(rng):
    x = np.concatenate([
        c + 0.2 * rng.normal(size=(300, 3))
        for c in (np.zeros(3), np.full(3, 8.0))
    ])
    model = KMeans().setK(2).setSeed(3).setUseXlaDot(False).fit(
        lambda: (x[i:i + 100] for i in range(0, len(x), 100))
    )
    # cost invariant: training_cost_ is measured under the returned centers
    assert abs(model.training_cost_ - model.compute_cost(x)) / model.training_cost_ < 1e-6


def test_kmeans_streamed_cost_matches_final_centers(rng):
    x = rng.normal(size=(1500, 4))
    model = KMeans().setK(5).setSeed(1).fit(
        lambda: (x[i:i + 400] for i in range(0, len(x), 400))
    )
    assert abs(model.training_cost_ - model.compute_cost(x)) / model.training_cost_ < 1e-4


def test_kmeans_streaming_requires_reiterable(rng):
    gen = iter([rng.normal(size=(100, 3))])
    with pytest.raises(ValueError, match="re-iterable"):
        KMeans().setK(2).fit(gen)
