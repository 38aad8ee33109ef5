"""Native runtime (libtpuml.so) unit tests — the layer the reference never
tested (SURVEY.md §4: "No unit tests of the native layer"). conftest.py
builds the library with make; skips if there is no toolchain.
"""

import os

import numpy as np
import pytest

from spark_rapids_ml_tpu import native

pytestmark = pytest.mark.skipif(
    not native.is_loaded(), reason="native toolchain unavailable"
)


def test_version():
    assert native.version().startswith("tpuml")


def test_gemm_matches_numpy(rng):
    a = rng.normal(size=(37, 23))
    b = rng.normal(size=(23, 11))
    np.testing.assert_allclose(native.gemm(a, b), a @ b, atol=1e-12)


def test_gram_matches_numpy(rng):
    a = rng.normal(size=(53, 17))
    np.testing.assert_allclose(native.gram(a), a.T @ a, atol=1e-11)


def test_gemm_shape_mismatch(rng):
    with pytest.raises(ValueError, match="shape mismatch"):
        native.gemm(np.ones((3, 4)), np.ones((5, 2)))


@pytest.mark.parametrize("transa,transb", [
    (False, False), (True, False), (False, True), (True, True),
])
def test_gemm_all_transpose_combos(rng, transa, transb):
    """Full cuBLAS-signature parity (RAPIDSML.scala:71-74): every
    transa×transb combo, with non-trivial alpha/beta."""
    m, n, kk = 19, 13, 29
    a = rng.normal(size=(kk, m) if transa else (m, kk))
    b = rng.normal(size=(n, kk) if transb else (kk, n))
    c0 = rng.normal(size=(m, n))
    op_a = a.T if transa else a
    op_b = b.T if transb else b
    expected = 0.75 * (op_a @ op_b) - 0.5 * c0
    got = native.gemm(a, b, transa=transa, transb=transb,
                      alpha=0.75, beta=-0.5, c=c0.copy())
    np.testing.assert_allclose(got, expected, atol=1e-12)


def test_gemm_reference_covariance_shape(rng):
    """The reference's live covariance call is gemm(OP_N, OP_T, n, n, m,
    1.0, B, B, 0.0, C) on column-major data (RapidsRowMatrix.scala:195-196)
    — in row-major terms, B·Bᵀ of the n×m layout. Pin the B·Bᵀ form."""
    bmat = rng.normal(size=(7, 31))
    got = native.gemm(bmat, bmat, transb=True)
    np.testing.assert_allclose(got, bmat @ bmat.T, atol=1e-12)


def test_syevd_matches_lapack(rng):
    x = rng.normal(size=(40, 12))
    cov = np.cov(x, rowvar=False)
    w, v = native.syevd(cov)
    w_np, v_np = np.linalg.eigh(cov)
    np.testing.assert_allclose(w, w_np, atol=1e-9)
    # eigenvectors up to sign
    np.testing.assert_allclose(np.abs(v), np.abs(v_np), atol=1e-8)
    # reconstruction: A = V diag(w) Vᵀ
    np.testing.assert_allclose(v @ np.diag(w) @ v.T, cov, atol=1e-9)


def test_syevd_identity():
    w, v = native.syevd(np.eye(5))
    np.testing.assert_allclose(w, np.ones(5), atol=1e-12)


def test_syevd_lapack_at_production_n(rng):
    """The host eigensolver must not be a toy: with the dlopen'd LAPACK
    dsyevd (the same divide-and-conquer core the reference reaches through
    cuSolver, rapidsml_jni.cu:338-392) an n=512 solve is sub-second and
    matches NumPy to 1e-10; the Jacobi fallback alone would need minutes at
    production n."""
    import time

    if not native.host_eigh_is_lapack():
        pytest.skip("no dlopen-able system LAPACK; Jacobi fallback in use")
    n = 512
    x = rng.normal(size=(n, n))
    cov = (x + x.T) / 2
    t0 = time.time()
    w, v = native.syevd(np.ascontiguousarray(cov))
    elapsed = time.time() - t0
    w_np, v_np = np.linalg.eigh(cov)
    np.testing.assert_allclose(w, w_np, atol=1e-10 * n)
    np.testing.assert_allclose(np.abs(v), np.abs(v_np), atol=1e-8)
    assert elapsed < 10.0, f"n={n} eigensolve took {elapsed:.1f}s"


def test_syevd_rejects_nonsquare():
    with pytest.raises(ValueError, match="square"):
        native.syevd(np.ones((3, 4)))


def test_trace_ranges_balanced():
    before = native.trace_event_count()
    native.trace_push("phase-a", 0xFFFF0000)
    native.trace_push("phase-b", 0xFF00FF00)
    assert native.trace_depth() == 2
    native.trace_pop()
    native.trace_pop()
    assert native.trace_depth() == 0
    assert native.trace_event_count() == before + 4


def test_trace_unbalanced_pop_is_safe():
    while native.trace_depth() > 0:
        native.trace_pop()
    native.trace_pop()  # extra pop must not crash or underflow
    assert native.trace_depth() == 0


def test_buffer_pool_reuse():
    lib = native.load()
    import ctypes

    p1 = lib.tpuml_alloc(1 << 20)
    assert p1
    assert native.pool_bytes_in_use() >= (1 << 20)
    lib.tpuml_free(ctypes.c_void_p(p1))
    assert native.pool_bytes_pooled() >= (1 << 20)
    p2 = lib.tpuml_alloc(1 << 20)  # exact-size bucket: reused block
    assert p2 == p1
    lib.tpuml_free(ctypes.c_void_p(p2))
    native.pool_trim()
    assert native.pool_bytes_pooled() == 0


def test_host_pca_path_uses_native(rng):
    # End-to-end: useXlaDot=False + useXlaSvd=False run through libtpuml.
    from spark_rapids_ml_tpu import PCA

    x = rng.normal(size=(60, 8))
    events_before = native.trace_event_count()
    model = PCA().setK(3).setUseXlaDot(False).setUseXlaSvd(False).fit(x)
    from conftest import numpy_pca_oracle

    pc, evr, _ = numpy_pca_oracle(x, 3)
    np.testing.assert_allclose(model.pc, pc, atol=1e-5)
    np.testing.assert_allclose(model.explained_variance, evr, atol=1e-5)
    # native trace ranges were recorded for the host phases
    assert native.trace_event_count() > events_before


def test_gemm_b_alpha_beta(rng):
    a = rng.normal(size=(21, 6))
    b = rng.normal(size=(21, 4))
    c0 = rng.normal(size=(6, 4))
    got = native.gemm_b(a, b, alpha=2.0, beta=0.25, c=c0.copy())
    np.testing.assert_allclose(got, 2.0 * (a.T @ b) + 0.25 * c0, atol=1e-12)


def test_gemm_b_matches_numpy(rng):
    # dgemm_b parity: C = AᵀB with alpha=1/beta=0 (rapidsml_jni.cu:260-336).
    a = rng.normal(size=(19, 7))
    b = rng.normal(size=(19, 5))
    np.testing.assert_allclose(native.gemm_b(a, b), a.T @ b, atol=1e-12)


def test_gemm_b_shape_mismatch(rng):
    with pytest.raises(ValueError, match="shape mismatch"):
        native.gemm_b(np.ones((3, 4)), np.ones((5, 2)))


def test_spr_accumulates_outer_product(rng):
    # dspr parity (rapidsml_jni.cu:107-170): packed upper-triangular
    # rank-1 updates sum to the Gram matrix.
    from spark_rapids_ml_tpu.linalg import triu_to_full

    x = rng.normal(size=(12, 6))
    packed = None
    for row in x:
        packed = native.spr(row, packed)
    np.testing.assert_allclose(triu_to_full(6, packed), x.T @ x, atol=1e-11)


def test_spr_alpha_and_length_check(rng):
    v = rng.normal(size=4)
    packed = native.spr(v, alpha=2.5)
    from spark_rapids_ml_tpu.linalg import triu_to_full

    np.testing.assert_allclose(triu_to_full(4, packed), 2.5 * np.outer(v, v),
                               atol=1e-12)
    with pytest.raises(ValueError, match="packed length"):
        native.spr(v, np.zeros(11))


# -- native PJRT client (tpuml_pjrt.cpp) ---------------------------------
# Exercising the real client needs a PJRT plugin and claims the accelerator,
# so the live path is opt-in (TPUML_PJRT_SMOKE=1, run on a quiet chip). The
# always-on tests cover the no-plugin behavior contract.


def test_pjrt_symbols_present():
    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable")
    assert lib.tpuml_pjrt_available() == 1


def test_pjrt_unavailable_paths_are_graceful(monkeypatch):
    # with no plugin configured, init reports False and the numpy-facing
    # wrappers raise RuntimeError (callers fall back to the JAX path)
    monkeypatch.setattr(native, "_pjrt_ready", False)
    monkeypatch.setattr(native, "pjrt_plugin_path", lambda: None)
    assert native.pjrt_init() in (False,) if native.load() is not None else True
    if native.load() is not None:
        with pytest.raises(RuntimeError):
            native.pjrt_gram(np.eye(4, dtype=np.float32))


@pytest.mark.skipif(
    os.environ.get("TPUML_PJRT_SMOKE") != "1",
    reason="live accelerator smoke test (set TPUML_PJRT_SMOKE=1)",
)
def test_pjrt_gram_and_dot_on_accelerator():
    assert native.pjrt_init(), native.pjrt_last_error()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 64)).astype(np.float32)
    np.testing.assert_allclose(native.pjrt_gram(x), x.T @ x, atol=5e-4)
    a = rng.normal(size=(96, 64)).astype(np.float32)
    b = rng.normal(size=(64, 8)).astype(np.float32)
    np.testing.assert_allclose(native.pjrt_dot(a, b), a @ b, atol=5e-4)
    native.pjrt_shutdown()


def test_jvm_shim_smoke_script():
    """SURVEY §7 step 2's JVM front-end seam: the Panama-FFI binding
    (native/jvm/TpuML.java) smoke runs when a JDK 22+ is present and
    skips cleanly otherwise (this image ships no JDK — same gating
    convention as the pyspark lane)."""
    import subprocess

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        ["bash", "native/jvm/run_smoke.sh"],
        capture_output=True, text=True, cwd=repo_root, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    out = proc.stdout
    assert ("SKIP" in out) or ("JVM smoke OK" in out), out
