"""The gated solve as one tracked program (``ops/eigh.py``): what runs,
how often it compiles, what it returns against the eager composition it
replaced, that a replaced solver retraces, that a passing gate never
touches the dense ``eigh``, and that the program compiles for a v5e."""

import os
import sys
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.obs import xprof
from spark_rapids_ml_tpu.obs.report import fit_instrumentation
from spark_rapids_ml_tpu.ops import eigh as eigh_ops
from spark_rapids_ml_tpu.ops import randomized
from spark_rapids_ml_tpu.ops.eigh import (
    explained_variance_ratio,
    pca_from_covariance_gated,
    sign_flip,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402

RANDOMIZED = "_randomized_solve_program"
DENSE = "_dense_solve_program"


def _decaying_cov(n, decay, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    lam = decay ** np.arange(n)
    return jnp.asarray((q * lam[None, :]) @ q.T, dtype=dtype)


@fit_instrumentation("solve_program_test")
def _observed_solve(cov, k, **kwargs):
    """The gated solve inside a fit context: the result carries the
    report (compiles, every executable JAX built, ``extra["solve"]``)."""
    return pca_from_covariance_gated(cov, k, **kwargs)


def _compiles(label):
    return xprof.compile_stats().get(label, {}).get("compiles", 0)


# -- the oracle: the solve as it was sequenced before, op by op, eagerly ----


def _eager_orthonormalize(y):
    eps = jnp.asarray(jnp.finfo(y.dtype).eps, y.dtype)
    tiny = jnp.asarray(jnp.finfo(y.dtype).tiny, y.dtype)

    def whiten(y, drop_unresolved):
        b = y.T @ y
        b = (b + b.T) / 2
        evals, vecs = jnp.linalg.eigh(b)
        floor = jnp.maximum(evals[-1] * eps * y.shape[0], tiny)
        inv_sqrt = 1.0 / jnp.sqrt(jnp.maximum(evals, floor))
        if drop_unresolved:
            inv_sqrt = jnp.where(evals > floor, inv_sqrt, 0.0)
        return y @ (vecs * inv_sqrt[None, :])

    return whiten(whiten(y, False), True)


def _eager_randomized(cov, k, oversample=10, n_iter=4, seed=0):
    n = cov.shape[0]
    l = min(k + oversample, n)
    with jax.default_matmul_precision("highest"):
        omega = jax.random.normal(jax.random.PRNGKey(seed), (n, l),
                                  dtype=cov.dtype)
        y = cov @ omega
        for _ in range(n_iter):
            q = _eager_orthonormalize(y)
            y = cov @ q
        q = _eager_orthonormalize(y)
        b = q.T @ (cov @ q)
        b = (b + b.T) / 2
        evals, vecs = jnp.linalg.eigh(b)
        evals, evecs = evals[::-1], (q @ vecs[:, ::-1])
    evecs = sign_flip(evecs)
    total = jnp.trace(cov)
    lam = jnp.maximum(evals[:k], 0.0)
    return evecs[:, :k], lam / jnp.where(total > 0, total, 1.0)


def _eager_gate(cov, pc, evr, k):
    lam = evr * jnp.trace(cov)
    resid = jnp.linalg.norm(cov @ pc - pc * lam[None, :])
    scale = jnp.sqrt(jnp.asarray(k, cov.dtype)) * jnp.maximum(
        jnp.mean(lam), jnp.finfo(cov.dtype).tiny)
    return float(resid / scale), float(jnp.min(jnp.sum(pc * pc, axis=0)))


# -- (a) one program, compiled once ------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_randomized_solve_is_one_tracked_program_compiled_once(dtype):
    n, k = 1056, 12  # a width no other test solves: a fresh signature
    cov = _decaying_cov(n, 0.9, dtype)
    before = _compiles(RANDOMIZED), _compiles(DENSE)
    first = _observed_solve(cov, k)
    assert first[2] == "randomized"
    rep = first.fit_report_
    # the tracked count and JAX's own count of executables agree: one
    assert rep.compiles == 1 and rep.recompiles in (0, 1)
    assert rep.programs_compiled + rep.programs_fetched == 1
    assert _compiles(RANDOMIZED) == before[0] + 1
    assert _compiles(DENSE) == before[1]
    assert rep.extra["solve"]["programs"] == 1
    second = _observed_solve(cov, k)
    rep = second.fit_report_
    assert rep.compiles == 0
    assert rep.programs_compiled == 0 and rep.programs_fetched == 0
    assert np.array_equal(np.asarray(first[0]), np.asarray(second[0]))
    assert np.array_equal(np.asarray(first[1]), np.asarray(second[1]))


# -- (b) the same arithmetic as the eager composition ------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_program_matches_the_eager_composition_to_rounding(dtype):
    n, k = 1024, 16
    cov = _decaying_cov(n, 0.9, dtype)
    out = _observed_solve(cov, k)
    pc, evr, used = out
    assert used == "randomized"
    pc_o, evr_o = _eager_randomized(cov, k)
    eps = float(jnp.finfo(dtype).eps)
    # same operations in the same order; XLA fuses one program otherwise
    # than 170, and adjacent eigenvalues 10 % apart pass a rounding on
    # tenfold. Measured here: components 5.6 eps (f32) and 4.5 eps (f64)
    # apart, variance ratios 8.5 and 10 eps
    assert float(jnp.max(jnp.abs(pc - pc_o))) < 200 * eps
    np.testing.assert_allclose(np.asarray(evr), np.asarray(evr_o),
                               rtol=200 * eps)
    assert pc.dtype == cov.dtype and evr.dtype == cov.dtype
    solve = out.fit_report_.extra["solve"]
    ratio_o, min_norm2_o = _eager_gate(cov, pc_o, evr_o, k)
    assert solve["solver"] == "randomized" and solve["gate"] == "passed"
    assert solve["programs"] == 1
    assert solve["residual_ratio"] == pytest.approx(ratio_o, rel=1e-2,
                                                    abs=100 * eps)
    assert solve["residual_ratio"] <= 0.05 and min_norm2_o > 0.5


def test_pca_fit_reports_the_solve(rng):
    n_feat, k = 1200, 8
    x = rng.normal(size=(400, 40)) * (0.85 ** np.arange(40))[None, :]
    x = x @ rng.normal(size=(40, n_feat))
    x = x + 0.01 * rng.normal(size=(400, n_feat))
    model = PCA().setK(k).fit(x)
    solve = model.fit_report_.extra["solve"]
    assert solve == {"solver": "randomized", "gate": "passed",
                     "residual_ratio": solve["residual_ratio"],
                     "programs": 1}
    assert 0.0 <= solve["residual_ratio"] <= 0.05
    assert model.svd_solver_used_ == "randomized"
    # host covariance, device solve: small n, so the dense program
    narrow = PCA().setK(4).setUseXlaDot(False).fit(x[:, :32])
    assert narrow.fit_report_.extra["solve"] == {
        "solver": "eigh", "gate": "ungated", "residual_ratio": None,
        "programs": 1}
    host = PCA().setK(4).setUseXlaSvd(False).setUseXlaDot(False).fit(
        x[:, :32])
    assert "solve" not in host.fit_report_.extra  # host LAPACK: no program


@pytest.mark.parametrize("solver, label", [("randomized", RANDOMIZED),
                                           ("eigh", DENSE)])
def test_mesh_streamed_fit_solves_in_one_program_too(rng, solver, label):
    """``distributed_streaming_pca_fit`` holds a concrete covariance like
    ``PCA.fit``: run eagerly, the rolled loops would be traced anew in
    every fit."""
    from spark_rapids_ml_tpu.data.batches import BatchSource
    from spark_rapids_ml_tpu.parallel import data_mesh
    from spark_rapids_ml_tpu.parallel.streaming import (
        distributed_streaming_pca_fit,
    )

    x = (rng.normal(size=(256, 24)) * 0.7 ** np.arange(24)).astype(np.float32)
    source = BatchSource(x, batch_rows=64)
    mesh = data_mesh(4)
    first = distributed_streaming_pca_fit(source, 4, mesh, solver=solver)
    compiled = _compiles(label)
    second = distributed_streaming_pca_fit(source, 4, mesh, solver=solver)
    assert _compiles(label) == compiled >= 1
    assert second.fit_report_.extra["solve"]["programs"] == 1
    assert second.fit_report_.extra["solve"]["solver"] == solver
    assert second.fit_report_.programs_compiled == 0
    np.testing.assert_array_equal(np.asarray(first.components),
                                  np.asarray(second.components))


# -- (c) a replaced solver retraces, and the loop runs what it is told -------


def test_replaced_solver_retraces_and_putting_it_back_restores_the_result(
        monkeypatch):
    """``benchmarks/sweep.py`` plants "one power iteration fewer" by
    replacing the module attribute after a sound fit in the same process;
    behind a cache keyed on shapes alone the sound model would come back
    under the fault's name."""
    n, k = 1024, 16
    cov = _decaying_cov(n, 0.9, jnp.float32, seed=3)
    real = randomized.randomized_pca_from_covariance
    sound = pca_from_covariance_gated(cov, k)
    monkeypatch.setattr(randomized, "randomized_pca_from_covariance",
                        partial(real, n_iter=3))
    fault = pca_from_covariance_gated(cov, k)
    monkeypatch.setattr(randomized, "randomized_pca_from_covariance", real)
    again = pca_from_covariance_gated(cov, k)
    assert sound[2] == fault[2] == again[2] == "randomized"
    assert not np.array_equal(np.asarray(sound[0]), np.asarray(fault[0]))
    assert float(jnp.max(jnp.abs(sound[1] - fault[1]))) > 0
    assert np.array_equal(np.asarray(sound[0]), np.asarray(again[0]))
    assert np.array_equal(np.asarray(sound[1]), np.asarray(again[1]))
    # and the fault is the eager solve with three iterations, not noise
    pc3, evr3 = _eager_randomized(cov, k, n_iter=3)
    np.testing.assert_allclose(np.asarray(fault[1]), np.asarray(evr3),
                               rtol=1e-4)


@pytest.mark.parametrize("n_iter, meets", [(4, True), (3, False)])
def test_rolled_loop_runs_the_iterations_it_is_told(monkeypatch, n_iter,
                                                    meets):
    """The convergence envelope of ``tests/test_chip_smoke.py``'s tail
    check, read through the gated program: four iterations meet it, three
    miss it (0.94 and 8.5 of the envelope there)."""
    shape = chip_smoke.Shape(n_features=512, k=64, in_memory_rows=4096, top=8)
    oracle = chip_smoke.oracle_pca(
        chip_smoke.make_rows(shape.in_memory_rows, shape.n_features), shape.k)
    bars = {"mean": 1e-6, "ortho": 1e-6, "pc_top": 1e-2, "evr_top": 1e-6,
            "evr_envelope": 3.0, "missed": 5e-2}
    real = randomized.randomized_pca_from_covariance
    if n_iter != 4:
        monkeypatch.setattr(randomized, "randomized_pca_from_covariance",
                            partial(real, n_iter=n_iter))
    pc, evr, used = pca_from_covariance_gated(
        jnp.asarray(oracle[3]), shape.k, solver="randomized")
    # the envelope itself is computed from the design's four iterations
    monkeypatch.setattr(randomized, "randomized_pca_from_covariance", real)
    assert used == "randomized"
    model = types.SimpleNamespace(
        pc=np.asarray(pc), mean=oracle[2], explained_variance=np.asarray(evr),
        svd_solver_used_="randomized")
    checks = chip_smoke.Checks()
    chip_smoke.check_against_oracle(checks, model, oracle, shape, bars)
    assert (checks.failed == []) == meets
    if not meets:
        assert any("envelope" in name for name in checks.failed)


# -- (d) the dense eigh only after the gate has failed -----------------------


@pytest.fixture
def dense_eigh_raises(monkeypatch):
    """``eigh_descending`` of the dense branch refuses a square input of
    ``n``; the randomized solve's own l×l factorizations do not pass
    through it."""
    real = eigh_ops.eigh_descending

    def arm(n):
        def guarded(cov):
            if cov.shape == (n, n):
                raise AssertionError(f"dense eigh traced at {n}×{n}")
            return real(cov)

        monkeypatch.setattr(eigh_ops, "eigh_descending", guarded)

    return arm


def test_a_passing_gate_never_traces_the_dense_eigh(dense_eigh_raises):
    n, k = 1088, 8  # fresh width: neither program is compiled for it yet
    cov = _decaying_cov(n, 0.9, jnp.float32)
    dense_eigh_raises(n)
    before = _compiles(DENSE)
    out = _observed_solve(cov, k)
    assert out[2] == "randomized"
    assert out.fit_report_.extra["solve"]["gate"] == "passed"
    assert _compiles(DENSE) == before
    # the guard is live: what the dense program traces trips it (the plain
    # function, so that no tracked program records a fallen-back signature)
    with pytest.raises(AssertionError, match="dense eigh traced"):
        eigh_ops.pca_from_covariance(cov, k, True, "eigh")


def _rank_deficient(n):
    a = np.random.default_rng(5).normal(size=(n, 40))
    return jnp.asarray(a @ a.T, dtype=jnp.float32)


@pytest.mark.parametrize("make, k, kwargs", [
    (lambda n: _decaying_cov(n, 0.9, jnp.float32), 16,
     {"residual_rtol": 1e-30}),
    (_rank_deficient, 64, {}),                       # a dropped direction
    (lambda n: jnp.full((n, n), jnp.nan, jnp.float32), 8,
     {"solver": "randomized"}),
], ids=["tiny_rtol", "dropped_direction", "nan_covariance"])
def test_a_failing_gate_runs_the_dense_program_second(make, k, kwargs):
    n = 128 if "solver" in kwargs else 1024
    cov = make(n)
    out = _observed_solve(cov, k, **kwargs)
    pc, evr, used = out
    assert used == "eigh(gated)"
    solve = out.fit_report_.extra["solve"]
    assert solve["solver"] == "eigh(gated)" and solve["gate"] == "fallback"
    assert solve["programs"] == 2
    ratio = solve["residual_ratio"]
    assert np.isnan(ratio) if "solver" in kwargs else ratio >= 0.0
    if "solver" not in kwargs:
        # what came back is the dense program's answer
        pc_d, evr_d = eigh_ops.pca_from_covariance(cov, k, True, "eigh")
        np.testing.assert_allclose(np.asarray(evr), np.asarray(evr_d),
                                   rtol=1e-4, atol=1e-7)
        assert np.abs(np.asarray(pc)).max(axis=0).min() > 0


def test_unknown_solver_is_refused_before_any_program():
    with pytest.raises(ValueError, match="expected 'eigh'"):
        pca_from_covariance_gated(jnp.eye(8), 2, solver="qr")


def test_traced_covariance_takes_the_static_choice_ungated():
    cov = _decaying_cov(64, 0.9, jnp.float32)

    @jax.jit
    def inside(c):
        pc, evr, used = pca_from_covariance_gated(c, 4, solver="randomized")
        assert used == "randomized"
        return pc, evr

    pc, evr = inside(cov)
    pc_o, evr_o = randomized.randomized_pca_from_covariance(
        cov, 4, jnp.trace(cov))
    np.testing.assert_allclose(np.asarray(evr), np.asarray(evr_o), rtol=1e-5)
    assert pc.shape == pc_o.shape


# -- (e) the dense branch is one program too ---------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_dense_branch_at_an_odd_width_is_one_program(dtype):
    n, k = 98, 7  # 784-like: not a multiple of the 128-lane tile
    cov = _decaying_cov(n, 0.9, dtype, seed=2)
    before = _compiles(DENSE), _compiles(RANDOMIZED)
    first = _observed_solve(cov, k)
    assert first[2] == "eigh"
    rep = first.fit_report_
    assert rep.compiles == 1
    assert rep.programs_compiled + rep.programs_fetched == 1
    assert (_compiles(DENSE), _compiles(RANDOMIZED)) == (before[0] + 1,
                                                         before[1])
    assert rep.extra["solve"] == {"solver": "eigh", "gate": "ungated",
                                  "residual_ratio": None, "programs": 1}
    rep = _observed_solve(cov, k).fit_report_
    assert rep.compiles == 0
    assert rep.programs_compiled == 0 and rep.programs_fetched == 0
    # against the plain functions, composed eagerly
    evals, evecs = jnp.linalg.eigh(cov)
    evals, evecs = evals[::-1], sign_flip(evecs[:, ::-1])
    eps = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        np.asarray(first[1]), np.asarray(explained_variance_ratio(evals)[:k]),
        rtol=100 * eps)
    assert float(jnp.max(jnp.abs(first[0] - evecs[:, :k]))) < 400 * eps


# -- the program compiles for the chip it is meant for -----------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_randomized_program_compiles_for_a_v5e_with_two_eigh_sites(
        one_chip, no_compilation_cache):
    """The fit path's program at n = 1024, k = 64 for the described chip
    (4096/256 takes 23 s and lives in ``PERF.md``). Every ``eigh`` site is
    compiled on its own, so the count of sites is the compile time: the
    rolled loops leave two (whitening, Rayleigh-Ritz), the unrolled form
    had eleven."""
    cov = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    lowered = eigh_ops._randomized_solve_program.lower(
        cov, 64, True, randomized.randomized_pca_from_covariance)
    # a site is one call of the factorization's private function; its
    # definition mentions the name once more
    hlo = lowered.as_text()
    sites = hlo.count("call @eigh")
    assert sites == 2, hlo.count("eigh")
    compiled = lowered.compile()
    outs = [(tuple(o.shape), str(o.dtype)) for o in compiled.out_info]
    assert outs == [((1024, 64), "float32"), ((64,), "float32"),
                    ((), "float32"), ((), "float32")]
    assert compiled.memory_analysis().generated_code_size_in_bytes < 16e6
