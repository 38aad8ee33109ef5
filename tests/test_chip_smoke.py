"""chip_smoke.py off the chip: it refuses to run without a TPU, its phases
work at a toy shape on the CPU backend, and the compile-cache helper
places the cache where it says."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Shape(n_features=64, k=8, in_memory_rows=1024,
                       stream_batch_rows=256, stream_cycles=2,
                       serve_max_batch_rows=32, top=4)
# x64 CPU arithmetic against a float64 oracle: everything is rounding error
TIGHT = {"mean": 1e-9, "ortho": 1e-9, "pc_top": 1e-7, "evr_top": 1e-9,
         "evr_all": 1e-9, "missed": 1e-9, "subspace": 1e-9}


def test_exits_nonzero_and_names_the_platform_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def toy_fits():
    # report_kernels reads the process-wide fallback count, as it must on
    # the chip; here the process is a worker other test files share, so the
    # count starts from these fits
    from spark_rapids_ml_tpu.obs.xprof import forget_fallback_signatures

    forget_fallback_signatures()
    x = chip_smoke.make_rows(TOY.in_memory_rows, TOY.n_features)
    in_memory = chip_smoke.fit_in_memory(x, TOY.k)
    streamed = chip_smoke.fit_streamed(x, TOY.k, TOY.stream_batch_rows,
                                       TOY.stream_cycles)
    return x, in_memory, streamed


def test_fit_phases_at_toy_shape(toy_fits):
    x, in_memory, streamed = toy_fits
    checks = chip_smoke.Checks()
    chip_smoke.report_fit(checks, "in-memory", in_memory, "cpu",
                          TOY.n_features)
    chip_smoke.report_fit(checks, "streamed", streamed, "cpu", TOY.n_features)
    chip_smoke.report_kernels(checks, "cpu", in_memory)
    oracle = chip_smoke.oracle_pca(x, TOY.k)
    chip_smoke.check_against_oracle(checks, in_memory, oracle, TOY, TIGHT)
    chip_smoke.check_fits_agree(checks, in_memory, streamed, TOY, TIGHT)
    chip_smoke.check_transform(checks, in_memory, x, 1e-9)
    assert checks.failed == []


def test_a_missed_bar_is_recorded(toy_fits):
    x, in_memory, _ = toy_fits
    checks = chip_smoke.Checks()
    wrong = chip_smoke.oracle_pca(x[::-1] * 2.0 + 1.0, TOY.k)
    chip_smoke.check_against_oracle(checks, in_memory, wrong, TOY, TIGHT)
    assert checks.failed
    checks.at_most("nan", float("nan"), 1.0)
    assert checks.failed[-1] == "nan"


def test_tail_check_tells_a_lost_iteration_from_the_design():
    """The TOY fits solve with dense eigh; the chip's shape solves with the
    randomized solver, whose unconverged tail is held against its own
    convergence envelope. One power iteration fewer must miss that bar."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.ops.randomized import (
        randomized_pca_from_covariance,
    )

    shape = chip_smoke.Shape(n_features=512, k=64, in_memory_rows=4096, top=8)
    oracle = chip_smoke.oracle_pca(
        chip_smoke.make_rows(shape.in_memory_rows, shape.n_features), shape.k)
    cov = jnp.asarray(oracle[3])
    # convergence, not rounding, is on trial: measured 0.94 of the envelope
    # with the default 4 iterations, 8.5 with 3
    bars = {"mean": 1e-6, "ortho": 1e-6, "pc_top": 1e-2, "evr_top": 1e-6,
            "evr_envelope": 3.0, "missed": 5e-2}

    def missed(**solver_args):
        pc, evr = randomized_pca_from_covariance(
            cov, shape.k, jnp.trace(cov), **solver_args)
        model = types.SimpleNamespace(
            pc=np.asarray(pc), mean=oracle[2],
            explained_variance=np.asarray(evr),
            svd_solver_used_="randomized")
        checks = chip_smoke.Checks()
        chip_smoke.check_against_oracle(checks, model, oracle, shape, bars)
        return checks.failed

    assert missed() == []
    assert any("envelope" in name for name in missed(n_iter=3))


def test_serve_phase_at_toy_shape(toy_fits):
    x, in_memory, _ = toy_fits
    checks = chip_smoke.Checks()
    batches = chip_smoke.serve_requests(checks, in_memory, x, TOY, "cpu", 1,
                                        1e-9)
    assert checks.failed == []
    assert list(batches) == ["TFRT_CPU_0"]


def test_compile_cache_helper_leaves_an_env_directory_alone(monkeypatch):
    import jax

    from spark_rapids_ml_tpu.utils import platform

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(platform.COMPILE_CACHE_ENV, "/somewhere/else")
    assert platform.configure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_helper_defaults_to_the_fixed_checkout_path(monkeypatch):
    import jax

    from spark_rapids_ml_tpu.utils import platform

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(platform.COMPILE_CACHE_ENV, raising=False)
    try:
        assert platform.configure_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
