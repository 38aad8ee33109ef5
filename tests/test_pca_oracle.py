"""Core correctness: every path combination vs the NumPy/LAPACK oracle.

Mirrors ``PCASuite``'s per-path coverage (SURVEY.md §4): "pca using spr"
(host/host), "pca using gemm" (device cov/host solve), "pca using cuSolver"
(host cov/device solve), defaults (device/device) — plus the
explainedVariance parity and rectangular-data tests the reference lacks.
Tolerance: absTol 1e-5, the reference's bar (``PCASuite.scala:71,106,141``).
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.ops.pca_kernel import pca_transform_kernel

from conftest import numpy_pca_oracle

ABS_TOL = 1e-5

PATHS = [
    (True, True),    # default: XLA cov + XLA eigh  ("gemm + cuSolver")
    (True, False),   # XLA cov + host solve          ("pca using gemm")
    (False, True),   # host cov + XLA eigh           ("pca using cuSolver")
    (False, False),  # host + host                   ("pca using spr")
]


@pytest.mark.parametrize("use_xla_dot,use_xla_svd", PATHS)
def test_fit_matches_oracle(rng, use_xla_dot, use_xla_svd):
    x = rng.normal(size=(60, 8))
    k = 5
    pc, evr, mean = numpy_pca_oracle(x, k)
    model = (
        PCA()
        .setK(k)
        .setUseXlaDot(use_xla_dot)
        .setUseXlaSvd(use_xla_svd)
        .fit(x)
    )
    np.testing.assert_allclose(model.pc, pc, atol=ABS_TOL)
    np.testing.assert_allclose(model.explained_variance, evr, atol=ABS_TOL)
    np.testing.assert_allclose(model.mean, mean, atol=ABS_TOL)


@pytest.mark.parametrize("use_xla_dot,use_xla_svd", PATHS)
def test_paths_agree_with_each_other(rng, use_xla_dot, use_xla_svd):
    # The reference's cuSolver test only compared |values| due to sign
    # ambiguity (PCASuite.scala:136-143); our sign-flip on every path makes
    # strict comparison possible.
    x = rng.normal(size=(40, 6))
    base = PCA().setK(4).fit(x)
    other = (
        PCA().setK(4).setUseXlaDot(use_xla_dot).setUseXlaSvd(use_xla_svd).fit(x)
    )
    np.testing.assert_allclose(other.pc, base.pc, atol=ABS_TOL)
    np.testing.assert_allclose(
        other.explained_variance, base.explained_variance, atol=ABS_TOL
    )


def test_rectangular_data_normalizer(rng):
    # Regression guard for the reference's numCols-vs-numRows normalizer bug
    # (RapidsRowMatrix.scala:169 vs :241, SURVEY.md §3.6): strongly
    # rectangular data must still match the oracle.
    x = rng.normal(size=(500, 7))
    pc, evr, _ = numpy_pca_oracle(x, 3)
    model = PCA().setK(3).fit(x)
    np.testing.assert_allclose(model.pc, pc, atol=ABS_TOL)
    np.testing.assert_allclose(model.explained_variance, evr, atol=ABS_TOL)


def test_mean_centering_false(rng):
    # Works on every path (the reference's spr path crashes, §3.6).
    x = rng.normal(loc=3.0, size=(50, 5))
    for dot, svd in PATHS:
        model = (
            PCA()
            .setK(2)
            .setMeanCentering(False)
            .setUseXlaDot(dot)
            .setUseXlaSvd(svd)
            .fit(x)
        )
        pc, evr, _ = numpy_pca_oracle(x, 2, mean_centering=False)
        np.testing.assert_allclose(model.pc, pc, atol=ABS_TOL)
        np.testing.assert_allclose(model.explained_variance, evr, atol=ABS_TOL)


def test_explained_variance_is_lambda_ratio(rng):
    # λ/Σλ (Spark CPU semantics), NOT √λ/Σ√λ (the reference GPU path's
    # inconsistency, rapidsml_jni.cu:377 + RapidsRowMatrix.scala:101-102).
    x = rng.normal(size=(100, 4)) * np.array([10.0, 5.0, 1.0, 0.1])
    model = PCA().setK(4).fit(x)
    cov = np.cov(x, rowvar=False)
    lam = np.sort(np.linalg.eigvalsh(cov))[::-1]
    np.testing.assert_allclose(
        model.explained_variance, lam / lam.sum(), atol=ABS_TOL
    )
    assert abs(float(np.sum(model.explained_variance)) - 1.0) < ABS_TOL


def test_k_equals_n_features(rng):
    x = rng.normal(size=(30, 5))
    model = PCA().setK(5).fit(x)
    assert model.pc.shape == (5, 5)
    # components orthonormal
    np.testing.assert_allclose(model.pc.T @ model.pc, np.eye(5), atol=1e-8)


def test_k_validation(rng):
    x = rng.normal(size=(10, 4))
    with pytest.raises(ValueError, match="at most"):
        PCA().setK(5).fit(x)
    with pytest.raises(ValueError, match="k must be set"):
        PCA().fit(x)


def test_transform_matches_oracle(rng):
    x = rng.normal(size=(50, 6))
    model = PCA().setK(3).fit(x)
    out = model.transform(x)
    got = np.asarray(out.column("pca_features"))
    # Spark semantics: projection of the RAW rows, no centering at
    # transform time (RapidsPCA.scala:187-189).
    np.testing.assert_allclose(got, x @ model.pc, atol=ABS_TOL)


def test_transform_host_path_agrees(rng):
    x = rng.normal(size=(50, 6))
    model = PCA().setK(3).fit(x)
    dev = np.asarray(model.transform(x).column("pca_features"))
    model.setUseXlaDot(False)
    host = np.asarray(model.transform(x).column("pca_features"))
    np.testing.assert_allclose(dev, host, atol=ABS_TOL)


def test_masked_fit_ignores_padding(rng):
    # Static-shape padding: the stream's padded tail (27 masked rows of a
    # 64-row batch, made garbage here) must not change results.
    from spark_rapids_ml_tpu.data.batches import BatchSource
    from spark_rapids_ml_tpu.ops.streaming import stream_covariance

    x = rng.normal(loc=2.0, size=(37, 5))

    class GarbageTail(BatchSource):
        def batches(self):
            for batch, mask in super().batches():
                batch[~mask] = 1e6
                yield batch, mask

    # a list of chunks: a matrix source would clamp the batch to its 37 rows
    cov, mean, count = stream_covariance(GarbageTail([x], batch_rows=64))
    assert int(count) == 37
    np.testing.assert_allclose(
        np.asarray(cov), np.cov(x, rowvar=False), atol=ABS_TOL)
    np.testing.assert_allclose(np.asarray(mean), x.mean(axis=0), atol=ABS_TOL)


def test_transform_kernel_batched(rng):
    import jax.numpy as jnp

    x = rng.normal(size=(20, 6))
    pc = rng.normal(size=(6, 3))
    out = pca_transform_kernel(jnp.asarray(x), jnp.asarray(pc))
    np.testing.assert_allclose(np.asarray(out), x @ pc, atol=1e-10)


def test_randomized_solver_matches_oracle_on_decaying_spectrum(rng):
    """svdSolver='randomized' must hit the oracle on a decaying spectrum —
    the regime the solver documents (ops/randomized.py caveat)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import PCA

    n, d, k = 400, 48, 6
    # strongly decaying spectrum: scale columns of an orthonormal basis
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    scales = 3.0 ** (-np.arange(d))
    x = rng.normal(size=(n, d)) @ (q * scales) + 5.0
    m_r = PCA().setK(k).setSvdSolver("randomized").fit(x)
    m_e = PCA().setK(k).setSvdSolver("eigh").fit(x)
    np.testing.assert_allclose(
        np.abs(np.asarray(m_r.pc)), np.abs(np.asarray(m_e.pc)), atol=5e-4
    )
    np.testing.assert_allclose(
        np.asarray(m_r.explained_variance),
        np.asarray(m_e.explained_variance),
        atol=5e-4,
    )


def test_randomized_solver_via_streaming_finalize(rng):
    """finalize_stats(solver='randomized') shares semantics with the
    one-shot randomized fit (same trace-exact λ/Σλ denominator)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.streaming import (
        StreamingPCA,
    )

    n, d, k = 300, 32, 4
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    x = (rng.normal(size=(n, d)) @ (q * 2.0 ** (-np.arange(d)))).astype(
        np.float32
    )
    s = StreamingPCA(d)
    for i in range(0, n, 100):
        s.partial_fit(jnp.asarray(x[i : i + 100]))
    res_r = s.finalize(k, solver="randomized")
    res_e = s.finalize(k, solver="eigh")
    np.testing.assert_allclose(
        np.abs(np.asarray(res_r.components)),
        np.abs(np.asarray(res_e.components)),
        atol=2e-3,
    )
    np.testing.assert_allclose(
        np.asarray(res_r.explained_variance),
        np.asarray(res_e.explained_variance),
        atol=2e-3,
    )


def test_invalid_svd_solver_rejected():
    from spark_rapids_ml_tpu import PCA

    with np.testing.assert_raises(ValueError):
        PCA().setSvdSolver("lanczos")
