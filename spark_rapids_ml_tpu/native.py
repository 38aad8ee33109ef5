"""ctypes bindings to the native C++ runtime ``libtpuml.so``.

The reference ships one native library, ``librapidsml_jni.so``, reached over
JNI with per-call device malloc/copy churn
(``/root/reference/src/main/java/com/nvidia/spark/ml/linalg/JniRAPIDSML.java:64-70``,
``native/src/rapidsml_jni.cu``). This framework's native runtime serves a
different role — the TPU compute path is XLA — but keeps native parity for
everything around it: host fallback kernels (gemm / syevd, mirroring
``dgemm``/``calSVD``), the batched transform (``dgemm_b``), trace range
markers (``NvtxRange push/pop``), and an aligned host buffer pool (what the
reference's RMM dependency should have been doing, SURVEY.md §2 checklist
item 6).

Loading is lazy and OPTIONAL: every caller falls back to NumPy when the
library is absent (the reference hard-requires its .so even on CPU paths —
a coupling we deliberately avoid, SURVEY.md §3.4). ``load()`` only loads
what exists; building is explicit — ``make -C native`` or ``build()`` —
so a trace range inside a fit never shells out to a compiler. Set
``SPARK_RAPIDS_ML_TPU_NATIVE=0`` to force the fallback, ``=require`` to fail
hard when missing.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_HERE)
_SO_CANDIDATES = (
    os.path.join(_HERE, "_native", "libtpuml.so"),
    os.path.join(_REPO_ROOT, "native", "build", "libtpuml.so"),
)


def build() -> Optional[str]:
    """Build the native library with make if the toolchain is present;
    returns the .so path, or None when it could not be built.

    Equivalent in spirit to the reference's Maven antrun step that drives
    cmake/ninja at build time (``pom.xml:337-360``). Explicit: tests and
    packaging call it, ``load()`` never does. Call before the first
    ``load()`` — the load decision is made once per process.
    """
    makefile_dir = os.path.join(_REPO_ROOT, "native")
    if not os.path.isfile(os.path.join(makefile_dir, "Makefile")):
        return None
    try:
        subprocess.run(
            ["make", "-s"],
            cwd=makefile_dir,
            check=True,
            capture_output=True,
            timeout=300,
        )
    except Exception:
        return None
    out = os.path.join(makefile_dir, "build", "libtpuml.so")
    return out if os.path.isfile(out) else None


def _configure(lib: ctypes.CDLL) -> None:
    d = ctypes.POINTER(ctypes.c_double)
    lib.tpuml_version.restype = ctypes.c_char_p
    lib.tpuml_trace_push.argtypes = [ctypes.c_char_p, ctypes.c_uint32]
    lib.tpuml_trace_push.restype = ctypes.c_int
    lib.tpuml_trace_pop.restype = ctypes.c_int
    lib.tpuml_trace_depth.restype = ctypes.c_int
    lib.tpuml_trace_event_count.restype = ctypes.c_longlong
    lib.tpuml_dgemm.argtypes = [
        ctypes.c_int, ctypes.c_int,                 # transa, transb
        ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,  # m, n, k
        ctypes.c_double, d, ctypes.c_longlong,      # alpha, A, lda
        d, ctypes.c_longlong,                       # B, ldb
        ctypes.c_double, d, ctypes.c_longlong,      # beta, C, ldc
    ]
    lib.tpuml_dgemm.restype = ctypes.c_int
    lib.tpuml_dgemm_b.argtypes = [
        ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_double, d, d, ctypes.c_double, d,  # alpha, A, B, beta, C
    ]
    lib.tpuml_dgemm_b.restype = ctypes.c_int
    lib.tpuml_dspr.argtypes = [ctypes.c_longlong, ctypes.c_double, d, d]
    lib.tpuml_dspr.restype = ctypes.c_int
    lib.tpuml_dsyevd.argtypes = [ctypes.c_longlong, d, d, d]
    lib.tpuml_dsyevd.restype = ctypes.c_int
    lib.tpuml_host_eigh_is_lapack.restype = ctypes.c_int
    lib.tpuml_alloc.argtypes = [ctypes.c_size_t]
    lib.tpuml_alloc.restype = ctypes.c_void_p
    lib.tpuml_free.argtypes = [ctypes.c_void_p]
    lib.tpuml_pool_bytes_in_use.restype = ctypes.c_size_t
    lib.tpuml_pool_bytes_pooled.restype = ctypes.c_size_t
    lib.tpuml_pool_trim.restype = None
    f = ctypes.POINTER(ctypes.c_float)
    lib.tpuml_pjrt_available.restype = ctypes.c_int
    lib.tpuml_pjrt_last_error.restype = ctypes.c_char_p
    lib.tpuml_pjrt_api_version.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)
    ]
    lib.tpuml_pjrt_api_version.restype = ctypes.c_int
    lib.tpuml_pjrt_init.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_longlong),
        ctypes.c_int,
    ]
    lib.tpuml_pjrt_init.restype = ctypes.c_int
    lib.tpuml_pjrt_device_count.restype = ctypes.c_int
    lib.tpuml_pjrt_compile.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t
    ]
    lib.tpuml_pjrt_compile.restype = ctypes.c_int
    lib.tpuml_pjrt_gram_f32.argtypes = [
        f, ctypes.c_longlong, ctypes.c_longlong, f
    ]
    lib.tpuml_pjrt_gram_f32.restype = ctypes.c_int
    lib.tpuml_pjrt_dot_tn_f32.argtypes = [
        f, f, ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong, f
    ]
    lib.tpuml_pjrt_dot_tn_f32.restype = ctypes.c_int
    lib.tpuml_pjrt_dot_nn_f32.argtypes = [
        f, f, ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong, f
    ]
    lib.tpuml_pjrt_dot_nn_f32.restype = ctypes.c_int
    lib.tpuml_pjrt_shutdown.restype = None


def load() -> Optional[ctypes.CDLL]:
    """Load the library if it has been built; returns None when absent."""
    global _lib, _load_attempted
    if _load_attempted:
        # Lock-free fast path once the load decision is final — trace
        # push/pop sit on per-phase hot paths and must not serialize
        # threads on _lock.
        return _lib
    with _lock:
        if _load_attempted:
            return _lib
        try:
            mode = os.environ.get("SPARK_RAPIDS_ML_TPU_NATIVE", "1")
            if mode == "0":
                return None
            path = next((p for p in _SO_CANDIDATES if os.path.isfile(p)), None)
            if path is None:
                if mode == "require":
                    raise OSError(
                        "libtpuml.so not found; build it with "
                        "`make -C native` or native.build()")
                return None
            try:
                lib = ctypes.CDLL(path)
                _configure(lib)
                _lib = lib
            except (OSError, AttributeError):
                # AttributeError: a stale .so missing a symbol (built
                # before a source update) — rebuild it explicitly
                _lib = None
                if mode == "require":
                    raise
            return _lib
        finally:
            # Set last (under the lock) so the lock-free fast path never
            # observes attempted=True with a half-configured _lib.
            _load_attempted = True


def is_loaded() -> bool:
    return load() is not None


def version() -> str:
    lib = load()
    if lib is None:
        raise OSError("native library not loaded")
    return lib.tpuml_version().decode()


# -- trace ranges (NvtxRange push/pop parity) ----------------------------
def trace_push(name: str, color: int = 0xFFFFFFFF) -> None:
    lib = load()
    if lib is not None:
        lib.tpuml_trace_push(name.encode(), ctypes.c_uint32(color & 0xFFFFFFFF))


def trace_pop() -> None:
    lib = load()
    if lib is not None:
        lib.tpuml_trace_pop()


def trace_depth() -> int:
    lib = load()
    return int(lib.tpuml_trace_depth()) if lib is not None else 0


def trace_event_count() -> int:
    lib = load()
    return int(lib.tpuml_trace_event_count()) if lib is not None else 0


# -- BLAS-like host kernels (dgemm / dgemm_b / calSVD parity) ------------
def _as_f64(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float64)


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def gemm(a: np.ndarray, b: np.ndarray, transa: bool = False,
         transb: bool = False, alpha: float = 1.0, beta: float = 0.0,
         c: Optional[np.ndarray] = None) -> np.ndarray:
    """C = α·op(A)·op(B) + β·C for row-major 2-D arrays — the full
    ``dgemm`` surface of ``RAPIDSML.scala:71-74`` (all four transpose
    combos; the reference's live covariance call uses OP_T,
    ``RapidsRowMatrix.scala:195-196``)."""
    lib = load()
    a, b = _as_f64(a), _as_f64(b)
    m, kk = (a.shape[1], a.shape[0]) if transa else a.shape
    k2, n = (b.shape[1], b.shape[0]) if transb else b.shape
    if kk != k2:
        raise ValueError(
            f"shape mismatch: op({a.shape}) @ op({b.shape})"
        )
    if c is None:
        c = np.zeros((m, n), dtype=np.float64)
    else:
        c = _as_f64(c)
        if c.shape != (m, n):
            raise ValueError(f"C has shape {c.shape}, expected {(m, n)}")
    if lib is None:
        op_a = a.T if transa else a
        op_b = b.T if transb else b
        # write THROUGH c like the native path does, so a caller-supplied
        # accumulator behaves identically with and without the .so
        np.copyto(c, alpha * (op_a @ op_b) + beta * c)
        return c
    rc = lib.tpuml_dgemm(
        int(transa), int(transb), m, n, kk, alpha,
        _ptr(a), a.shape[1], _ptr(b), b.shape[1], beta, _ptr(c), n
    )
    if rc != 0:
        raise RuntimeError(f"tpuml_dgemm failed with code {rc}")
    return c


def gram(a: np.ndarray) -> np.ndarray:
    """AᵀA (the covariance-assembly GEMM, transa=T shape)."""
    lib = load()
    a = _as_f64(a)
    m, n = a.shape
    if lib is None:
        return a.T @ a
    c = np.zeros((n, n), dtype=np.float64)
    rc = lib.tpuml_dgemm(
        1, 0, n, n, m, 1.0, _ptr(a), n, _ptr(a), n, 0.0, _ptr(c), n
    )
    if rc != 0:
        raise RuntimeError(f"tpuml_dgemm failed with code {rc}")
    return c


def gemm_b(a: np.ndarray, b: np.ndarray, alpha: float = 1.0,
           beta: float = 0.0, c: Optional[np.ndarray] = None) -> np.ndarray:
    """C = α·AᵀB + β·C (the batched-transform ``dgemm_b`` surface,
    ``rapidsml_jni.cu:260-336``, widened with the α/β the reference
    hardcoded to 1/0). ``a`` is k×m, ``b`` is k×n."""
    lib = load()
    a, b = _as_f64(a), _as_f64(b)
    k, m = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"shape mismatch: {a.shape}ᵀ @ {b.shape}")
    if c is None:
        c = np.zeros((m, n), dtype=np.float64)
    else:
        c = _as_f64(c)
        if c.shape != (m, n):
            raise ValueError(f"C has shape {c.shape}, expected {(m, n)}")
    if lib is None:
        np.copyto(c, alpha * (a.T @ b) + beta * c)
        return c
    rc = lib.tpuml_dgemm_b(m, n, k, alpha, _ptr(a), _ptr(b), beta, _ptr(c))
    if rc != 0:
        raise RuntimeError(f"tpuml_dgemm_b failed with code {rc}")
    return c


def spr(x: np.ndarray, packed: Optional[np.ndarray] = None,
        alpha: float = 1.0) -> np.ndarray:
    """Packed upper-triangular rank-1 update ``AP += α·x·xᵀ`` (the ``dspr``
    surface, ``rapidsml_jni.cu:107-170``); column-major packed layout,
    element (i, j) at ``AP[j(j+1)/2 + i]`` for i ≤ j."""
    x = _as_f64(x).reshape(-1)
    n = x.shape[0]
    plen = n * (n + 1) // 2
    if packed is None:
        packed = np.zeros(plen, dtype=np.float64)
    else:
        if not (
            isinstance(packed, np.ndarray)
            and packed.dtype == np.float64
            and packed.flags.c_contiguous
        ):
            # A silent ascontiguousarray copy would break the documented
            # in-place semantics (updates landing in a private copy).
            raise ValueError(
                "packed must be a C-contiguous float64 array (updated "
                "in place); got "
                f"dtype={getattr(packed, 'dtype', type(packed).__name__)}"
            )
        if packed.shape != (plen,):
            raise ValueError(
                f"packed length {packed.shape} does not match n={n} "
                f"(expected {plen})"
            )
    lib = load()
    if lib is None:
        rows, cs = np.triu_indices(n)
        packed[cs * (cs + 1) // 2 + rows] += alpha * x[rows] * x[cs]
        return packed
    rc = lib.tpuml_dspr(n, float(alpha), _ptr(x), _ptr(packed))
    if rc != 0:
        raise RuntimeError(f"tpuml_dspr failed with code {rc}")
    return packed


def syevd(a: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric eigendecomposition, ascending eigenvalues (``calSVD``'s
    eigDC core). Returns (eigenvalues, eigenvectors-as-columns)."""
    lib = load()
    a = _as_f64(a)
    n = a.shape[0]
    if a.shape != (n, n):
        raise ValueError("syevd requires a square matrix")
    if lib is None:
        return np.linalg.eigh(a)
    evals = np.zeros(n, dtype=np.float64)
    evecs = np.zeros((n, n), dtype=np.float64)
    rc = lib.tpuml_dsyevd(n, _ptr(a), _ptr(evals), _ptr(evecs))
    if rc != 0:
        raise RuntimeError(f"tpuml_dsyevd failed with code {rc}")
    # C layer returns eigenvectors row-major with vector j in column j.
    return evals, evecs


def host_eigh_is_lapack() -> bool:
    """Whether ``syevd`` runs on a dlopen'd system LAPACK ``dsyevd_``
    (production solver) rather than the built-in Jacobi fallback."""
    lib = load()
    if lib is None:
        return False
    return bool(lib.tpuml_host_eigh_is_lapack())


# -- host buffer pool ----------------------------------------------------
def pool_bytes_in_use() -> int:
    lib = load()
    return int(lib.tpuml_pool_bytes_in_use()) if lib is not None else 0


def pool_bytes_pooled() -> int:
    lib = load()
    return int(lib.tpuml_pool_bytes_pooled()) if lib is not None else 0


def pool_trim() -> None:
    lib = load()
    if lib is not None:
        lib.tpuml_pool_trim()


# -- PJRT accelerator path ----------------------------------------------
# The C++ layer speaks the XLA PJRT C API directly (native/src/
# tpuml_pjrt.cpp): compile StableHLO, own device buffers, execute on the
# accelerator with no Python in the loop — the true native counterpart of
# the reference's cuBLAS entry points (SURVEY.md §7 step 2). The plugin
# (.so implementing GetPjrtApi) is named by ``TPUML_PJRT_PLUGIN``.
#
# This is a SECOND PJRT client: an accelerator belongs to one process and
# one client, so never create it in a process where JAX already holds the
# chip (the chip smoke stays clear of it).

_pjrt_ready = False


def pjrt_plugin_path() -> Optional[str]:
    """The PJRT plugin to load: the ``TPUML_PJRT_PLUGIN`` env var."""
    env = os.environ.get("TPUML_PJRT_PLUGIN")
    return env if env and os.path.isfile(env) else None


def pjrt_init(
    plugin: Optional[str] = None,
    options: Optional[list] = None,
) -> bool:
    """Create the native PJRT client (idempotent). Returns False when the
    native library or a plugin is unavailable — callers fall back to the
    JAX path, same optional-native posture as the host kernels."""
    global _pjrt_ready
    lib = load()
    if lib is None:
        return False
    if _pjrt_ready:
        return True
    plugin = plugin or pjrt_plugin_path()
    if plugin is None:
        return False
    opts = options or []
    n = len(opts)
    names = (ctypes.c_char_p * n)()
    kinds = (ctypes.c_int * n)()
    svals = (ctypes.c_char_p * n)()
    ivals = (ctypes.c_longlong * n)()
    for i, (name, val) in enumerate(opts):
        names[i] = name.encode()
        if isinstance(val, str):
            kinds[i], svals[i] = 0, val.encode()
        else:
            kinds[i], ivals[i] = 1, int(val)
    rc = lib.tpuml_pjrt_init(plugin.encode(), names, kinds, svals, ivals, n)
    if rc != 0:
        return False
    _pjrt_ready = True
    return True


def pjrt_last_error() -> str:
    lib = load()
    return lib.tpuml_pjrt_last_error().decode() if lib is not None else ""


def pjrt_device_count() -> int:
    lib = load()
    if lib is None or not _pjrt_ready:
        return 0
    n = lib.tpuml_pjrt_device_count()
    return max(0, int(n))


def _as_f32(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.float32)


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def pjrt_gram(x: np.ndarray) -> np.ndarray:
    """G = XᵀX on the accelerator via the native client (the ``dgemm``
    covariance shape, ``rapidsml_jni.cu:172-258``)."""
    if not pjrt_init():
        raise RuntimeError(f"native PJRT unavailable: {pjrt_last_error()}")
    lib = load()
    x = _as_f32(x)
    m, n = x.shape
    out = np.zeros((n, n), dtype=np.float32)
    rc = lib.tpuml_pjrt_gram_f32(_fptr(x), m, n, _fptr(out))
    if rc != 0:
        raise RuntimeError(f"tpuml_pjrt_gram_f32: {pjrt_last_error()}")
    return out


def pjrt_dot(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """C = A·B on the accelerator (the batched-transform shape the
    reference left disabled, ``RapidsPCA.scala:172-185``)."""
    if not pjrt_init():
        raise RuntimeError(f"native PJRT unavailable: {pjrt_last_error()}")
    lib = load()
    a, b = _as_f32(a), _as_f32(b)
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"shape mismatch: {a.shape} @ {b.shape}")
    out = np.zeros((m, n), dtype=np.float32)
    rc = lib.tpuml_pjrt_dot_nn_f32(_fptr(a), _fptr(b), m, k, n, _fptr(out))
    if rc != 0:
        raise RuntimeError(f"tpuml_pjrt_dot_nn_f32: {pjrt_last_error()}")
    return out


def pjrt_shutdown() -> None:
    global _pjrt_ready
    lib = load()
    if lib is not None and _pjrt_ready:
        lib.tpuml_pjrt_shutdown()
    _pjrt_ready = False
