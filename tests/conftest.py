"""Test config: 8 virtual CPU devices + float64 parity mode.

Mirrors the reference's test posture (SURVEY.md §4): correctness is judged
against a CPU oracle at absTol 1e-5, and the distributed logic is exercised
with multiple devices in one process — here a virtual 8-device CPU mesh
(`xla_force_host_platform_device_count`), the TPU analogue of
``sc.parallelize(data, 2)`` giving 2 in-JVM partitions
(``PCASuite.scala:48``). x64 is enabled so parity tests run at the
reference's double precision.
"""

import os

# Tests are CPU-only by design; JAX honours the variable.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The auto-incident engine (obs.incidents) runs inside every serve
# server's sampler by default, and the fault-injection tests
# legitimately open incidents. Keep the engine ON (that path is under
# test) but disable incident-TRIGGERED profile captures suite-wide: a
# jax start_trace under live CPU traffic can wedge (obs/profiler.py),
# and a capture helper thread abandoned at interpreter teardown can
# crash it. The capture trigger itself is unit-tested with a stub.
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S", "0")

# The 8-device mesh above exists for the DISTRIBUTED-FIT tests. The
# serving tier would replicate every engine onto all 8 (its production
# default), but the legacy serve suites assert single-queue contracts —
# queue-full admission, preemption, one batcher per model, signature
# counts per bucket ladder — that are single-replica properties by
# design. Pin the suite default to ONE replica; the multi-device suite
# (tests/test_serve_multidevice.py) opts into N replicas explicitly per
# engine via the ``replicas=`` / ``placement=`` constructor args, which
# override this env default.
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", "1")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from spark_rapids_ml_tpu import native  # noqa: E402

# native.load() only loads what exists, so the suite builds the native
# runtime explicitly, once, before anything asks for it: the host-fallback
# paths then run their native arm and tests/test_native.py has a library.
native.build()


# Triage mark for a pre-existing env-limited failure: applied at the
# affected test definitions so the tier-1 signal is clean without masking
# anything this container could actually detect. (The optax L-BFGS tests
# carry no mark: optax 0.2.6 no longer raises under x64, so they run —
# tests/test_distributed.py::test_distributed_mlp_fit fails there because
# the linesearch diverges silently on a float32 objective under x64; with
# x64 off, as on the chip, it converges. Failing, not skipped.)
# NOTE: plugin-presence detection cannot gate this — this container ships
# libtpu with no reachable device, so only an explicit opt-in is reliable.
multiprocess_cpu_skip = pytest.mark.skipif(
    os.environ.get("SPARKML_RUN_MULTIPROCESS_TESTS") != "1",
    reason="multiprocess-on-CPU env limit: spawned worker processes joining "
           "one jax.distributed CPU job in this single-host container "
           "wedge/diverge (pre-existing seed failure). Set "
           "SPARKML_RUN_MULTIPROCESS_TESTS=1 to re-arm on hosts with "
           "working multi-process device coordination (real TPU CI).",
)


@pytest.fixture(autouse=True)
def _reset_leaked_incident_engine():
    """Any test that touches ``start_serve_server`` installs the
    process-wide auto-incident engine on the process-wide sampler. Left
    running, it keeps detecting against whatever the test left in the
    global registry (a fault-storm SLO burn gauge frozen at 500, say)
    and writes incident flight dumps into LATER tests' dump dirs. The
    engine is per-server-session state; drop a leaked one at teardown
    (tests that manage it themselves already reset to None first)."""
    yield
    from spark_rapids_ml_tpu.obs import incidents

    if incidents._engine is not None:
        incidents.reset_incident_engine()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def numpy_pca_oracle(x: np.ndarray, k: int, mean_centering: bool = True):
    """Reference oracle: NumPy/LAPACK PCA with the framework's documented
    semantics (numRows−1 normalizer, λ/Σλ, sign-flip). Plays the role Spark
    CPU MLlib plays in ``PCASuite`` (``PCASuite.scala:50-54``)."""
    x = np.asarray(x, dtype=np.float64)
    mean = x.mean(axis=0) if mean_centering else np.zeros(x.shape[1])
    xc = x - mean
    cov = xc.T @ xc / max(x.shape[0] - 1, 1)
    evals, evecs = np.linalg.eigh(cov)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    idx = np.argmax(np.abs(evecs), axis=0)
    signs = np.where(evecs[idx, np.arange(evecs.shape[1])] < 0, -1.0, 1.0)
    evecs = evecs * signs[None, :]
    lam = np.maximum(evals, 0)
    evr = lam / lam.sum() if lam.sum() > 0 else lam
    return evecs[:, :k], evr[:k], mean
