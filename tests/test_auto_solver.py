"""svdSolver='auto': shape heuristic, residual gate, model bookkeeping."""

import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.ops.eigh import (
    pca_from_covariance_gated,
    resolve_auto_solver,
)


def _decaying_cov(rng, n, decay=0.9):
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    lam = decay ** np.arange(n)
    return (q * lam[None, :]) @ q.T


def test_resolve_auto_solver_shape_heuristic():
    assert resolve_auto_solver(4096, 256) == "randomized"
    assert resolve_auto_solver(784, 50) == "eigh"        # n too small
    assert resolve_auto_solver(2048, 512) == "eigh"      # k not << n
    assert resolve_auto_solver(1024, 128) == "randomized"


def test_gated_randomized_matches_oracle_on_decaying_spectrum(rng):
    import jax.numpy as jnp

    n, k = 1024, 16
    cov = _decaying_cov(rng, n)
    pc, evr, used = pca_from_covariance_gated(jnp.asarray(cov), k)
    assert used == "randomized"
    evals, evecs = np.linalg.eigh(cov)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    idx = np.argmax(np.abs(evecs), axis=0)
    signs = np.where(evecs[idx, np.arange(n)] < 0, -1.0, 1.0)
    evecs = evecs * signs[None, :]
    # per-vector convergence rate is set by the adjacent gap ratio (0.9
    # here — slow); 1e-3 is the documented envelope for this spectrum
    np.testing.assert_allclose(np.asarray(pc), evecs[:, :k], atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(evr), evals[:k] / evals.sum(), atol=1e-6
    )


def test_gate_falls_back_to_eigh_when_residual_bar_unmet(rng):
    import jax.numpy as jnp

    cov = _decaying_cov(rng, 1024)
    pc, evr, used = pca_from_covariance_gated(
        jnp.asarray(cov), 16, residual_rtol=-1.0
    )
    assert used == "eigh(gated)"
    # the fallback result is the dense-eigh result: exact oracle parity
    evals, _ = np.linalg.eigh(cov)
    np.testing.assert_allclose(
        np.asarray(evr), evals[::-1][:16] / evals.sum(), atol=1e-10
    )


def test_float32_randomized_keeps_every_direction_on_a_power_law(rng):
    """How the chip runs (float32, variances ∝ 1/j — chip_smoke.py's
    spectrum): a single eigh-whitening pass resolves a singular-value
    range of 1/√(eps·n) ≈ 90 at n=1024, the sketch spans λ₁/λ_l = 128, and
    dropping the rest left zero columns that the residual gate waved
    through (PR 21, found on the v5e at 4096/k=256)."""
    import jax.numpy as jnp

    n, k = 1024, 118
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    lam = 1.0 / (1.0 + np.arange(n))
    cov = (q * lam[None, :]) @ q.T
    pc, evr, used = pca_from_covariance_gated(
        jnp.asarray(cov, dtype=jnp.float32), k)
    pc = np.asarray(pc, dtype=np.float64)
    assert used == "randomized"
    assert np.abs(pc.T @ pc - np.eye(k)).max() < 1e-4
    # the fitted subspace holds all but a sliver of the top-k variance
    assert 1.0 - np.trace(pc.T @ cov @ pc) / lam[:k].sum() < 5e-3
    assert np.all(np.asarray(evr) > 0)


def test_gate_falls_back_when_a_direction_was_dropped(rng):
    """k beyond rank(Cov): the orthonormalization zeroes what is not
    there, a zero column has zero residual, and the gate must still send
    the solve to dense eigh."""
    import jax.numpy as jnp

    a = rng.normal(size=(1024, 40))
    pc, _, used = pca_from_covariance_gated(
        jnp.asarray(a @ a.T, dtype=jnp.float32), 64)
    assert used == "eigh(gated)"
    assert np.abs(np.asarray(pc)).max(axis=0).min() > 0


def test_float32_zero_covariance_gives_zero_components_not_nan():
    """Constant data: Cov = 0, so Y = Cov·Ω = 0, λmax = 0 and a floor of
    λmax·eps·n is 0 — the clamping pass must not turn that into 0·inf.
    The ungated callers (jitted kernels, the feature-sharded fit) return
    whatever the solve returns."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.randomized import (
        _orthonormalize,
        randomized_pca_from_covariance,
    )

    q = np.asarray(_orthonormalize(jnp.zeros((256, 12), jnp.float32)))
    assert np.array_equal(q, np.zeros_like(q))
    cov = jnp.zeros((256, 256), jnp.float32)
    pc, evr = randomized_pca_from_covariance(cov, 8, jnp.trace(cov))
    assert np.array_equal(np.asarray(pc), np.zeros((256, 8), np.float32))
    assert np.array_equal(np.asarray(evr), np.zeros(8, np.float32))


def test_small_covariance_auto_is_eigh(rng):
    import jax.numpy as jnp

    cov = _decaying_cov(rng, 64)
    _, _, used = pca_from_covariance_gated(jnp.asarray(cov), 8)
    assert used == "eigh"


def test_pca_model_records_solver_choice(rng):
    x = rng.normal(size=(200, 32))
    model = PCA().setK(4).fit(x)
    assert model.svd_solver_used_ == "eigh"   # n=32 < 1024 → dense
    host = PCA().setK(4).setUseXlaSvd(False).setUseXlaDot(False).fit(x)
    assert host.svd_solver_used_ is None      # host LAPACK path
    explicit = PCA().setK(4).setSvdSolver("randomized").fit(x)
    assert explicit.svd_solver_used_ == "randomized"


def test_pca_auto_picks_randomized_on_wide_data(rng):
    # 1200 features, k=8: the streamed/gated path should choose and keep
    # the randomized solve, and still match the oracle subspace on a
    # decaying spectrum
    n_feat, k = 1200, 8
    x = rng.normal(size=(400, 40)) * (0.85 ** np.arange(40))[None, :]
    x = x @ rng.normal(size=(40, n_feat))
    x = x + 0.01 * rng.normal(size=(400, n_feat))
    model = PCA().setK(k).fit(x)
    assert model.svd_solver_used_ in ("randomized", "eigh(gated)")
    # projection quality: captured variance within 1% of the oracle's
    xc = x - x.mean(axis=0)
    cov = xc.T @ xc / (x.shape[0] - 1)
    evals = np.linalg.eigvalsh(cov)[::-1]
    pc = np.asarray(model.pc)
    captured = np.trace(pc.T @ cov @ pc)
    assert captured >= 0.99 * evals[:k].sum()
