"""Compile telemetry (obs.xprof): tracked_jit caching, recompile keying,
HLO cost analysis on the CPU backend, storm warnings, and the FitReport
compile/FLOPs plumbing."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import obs
from spark_rapids_ml_tpu.obs import (
    compile_stats,
    current_fit,
    fit_instrumentation,
    tracked_jit,
)


def _stats_for(fn):
    return compile_stats().get(fn.label, {})


def test_single_signature_compiles_once():
    calls = []

    @tracked_jit(label="xprof_once")
    def f(x):
        calls.append(1)
        return x * 2.0

    a = jnp.ones((4, 3))
    before = _stats_for(f).get("compiles", 0)
    r1 = f(a)
    r2 = f(a)
    np.testing.assert_allclose(np.asarray(r1), 2.0)
    np.testing.assert_allclose(np.asarray(r2), 2.0)
    after = _stats_for(f)
    assert after["compiles"] == before + 1
    assert after["recompiles"] == 0
    assert after["compile_seconds"] > 0
    # traced exactly once: the second call hit the compiled executable
    assert len(calls) == 1
    assert f.stats()["signatures"] == 1


def test_recompile_keyed_on_shape_and_dtype():
    @tracked_jit(label="xprof_rekey")
    def f(x):
        return x + 1.0

    f(jnp.ones((4, 2), dtype=jnp.float32))
    assert _stats_for(f)["recompiles"] == 0
    # shape change -> recompile
    f(jnp.ones((8, 2), dtype=jnp.float32))
    assert _stats_for(f)["recompiles"] == 1
    # dtype change -> recompile
    f(jnp.ones((8, 2), dtype=jnp.float64))
    assert _stats_for(f)["recompiles"] == 2
    # previously seen signature -> cache hit, no new compile
    f(jnp.ones((4, 2), dtype=jnp.float32))
    assert _stats_for(f)["compiles"] == 3
    assert f.stats()["signatures"] == 3


def test_static_argument_change_recompiles():
    @tracked_jit(label="xprof_static", static_argnames=("k",))
    def f(x, k):
        return x * k

    x = jnp.ones(4)
    f(x, 2)
    f(x, 2)
    assert _stats_for(f)["compiles"] == 1
    f(x, 3)
    assert _stats_for(f)["compiles"] == 2
    # positional-vs-keyword spelling of the same static is ONE signature
    f(x, k=3)
    assert _stats_for(f)["compiles"] == 2


def test_cost_analysis_flops_on_cpu_backend():
    """HLO cost_analysis works on the CPU backend and its FLOPs are in the
    right ballpark for a matmul (2·m·n·k)."""
    m, n, k = 32, 16, 24

    @tracked_jit(label="xprof_matmul")
    def f(a, b):
        return a @ b

    out = f(jnp.ones((m, k)), jnp.ones((k, n)))
    assert out.shape == (m, n)
    events = [e for e in obs.compile_log() if e.label == "xprof_matmul"]
    assert events
    ev = events[-1]
    assert ev.flops is not None and ev.flops >= 2 * m * n * k
    assert ev.bytes_accessed is not None and ev.bytes_accessed > 0
    assert ev.memory.get("output_size_in_bytes", 0) > 0


def test_donated_buffers_survive_tracking():
    @tracked_jit(label="xprof_donate", donate_argnums=(0,))
    def acc(s, b):
        return s + b

    s = jnp.zeros(4)
    b = jnp.ones(4)
    for _ in range(3):
        s = acc(s, b)
    np.testing.assert_allclose(np.asarray(s), 3.0)
    assert _stats_for(acc)["compiles"] == 1


def test_tracer_inputs_bypass_tracking():
    @tracked_jit(label="xprof_inner")
    def inner(x):
        return x * 2.0

    before = _stats_for(inner).get("compiles", 0)

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    out = outer(jnp.ones(3))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    # the inner tracked fn saw tracers and stayed out of the way: no
    # compile event of its own was logged
    assert _stats_for(inner).get("compiles", 0) == before


def test_recompile_storm_warning():
    @tracked_jit(label="xprof_storm", storm_threshold=3)
    def f(x):
        return x.sum()

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for n in range(1, 6):
            f(jnp.ones(n))
    storm = [w for w in caught if "recompile storm" in str(w.message)]
    assert len(storm) == 1  # loud, but once
    assert "xprof_storm" in str(storm[0].message)


def test_fit_context_accumulates_compiles_and_flops():
    @tracked_jit(label="xprof_fitctx")
    def kernel(x):
        return x @ x.T

    @fit_instrumentation("xprof_fit_test")
    def fake_fit(x):
        ctx = current_fit()
        with ctx.phase("execute"):
            return kernel(x)

    x = jnp.ones((13, 7))  # deliberately unusual shape: fresh signature
    out = fake_fit(x)
    rep = out.fit_report_
    assert rep.compiles >= 1
    assert rep.compile_seconds > 0
    assert rep.recompiles == 0
    assert rep.phases["execute"] > 0
    # the compile is cached: an identical second fit compiles nothing
    out2 = fake_fit(x)
    rep2 = out2.fit_report_
    assert rep2.compiles == 0
    assert rep2.compile_seconds == 0.0
    # the report carries counts, not an MFU from HLO cost analysis
    assert not hasattr(rep2, "analytic_flops")
    assert not hasattr(current_fit(), "record_program")


def test_phase_mfu_and_peak_helpers():
    from spark_rapids_ml_tpu.obs.report import FitReport

    rep = FitReport(
        algo="x", trace_id="t", started_utc="now", wall_seconds=2.0,
        phases={"execute": 1.0},
    )
    for gone in ("analytic_flops", "analytic_bytes", "flops_by_phase",
                 "analytic_mfu", "phase_mfu"):
        assert not hasattr(rep, gone) and gone not in rep.as_dict()
    # CPU backend has no published peak: analytic_mfu degrades to None
    assert obs.peak_flops_per_second() is None
    assert obs.analytic_mfu(1e12, 1.0) is None


def test_estimator_reports_carry_compile_and_memory_fields(rng):
    """Acceptance: a CPU-run PCA and KMeans fit report compile time,
    recompile count, the executables JAX built, and peak device bytes."""
    from spark_rapids_ml_tpu import KMeans, PCA

    x = rng.normal(size=(48, 6))
    for model in (PCA().setK(3).fit(x), KMeans().setK(2).fit(x)):
        rep = model.fit_report_
        assert isinstance(rep.compiles, int)
        assert isinstance(rep.recompiles, int)
        assert rep.compile_seconds >= 0.0
        assert rep.programs_compiled >= 0 and rep.programs_fetched >= 0
        assert rep.peak_device_bytes and rep.peak_device_bytes > 0
        assert rep.memory["source"] in ("pjrt", "host_rss")
        doc = rep.as_dict()
        for key in ("compiles", "recompiles", "compile_seconds",
                    "programs_compiled", "programs_fetched",
                    "peak_device_bytes"):
            assert key in doc


def test_distributed_driver_reports_compile_fields(rng):
    from spark_rapids_ml_tpu.parallel import data_mesh
    from spark_rapids_ml_tpu.parallel.distributed_pca import (
        distributed_pca_fit,
    )

    x = rng.normal(size=(40, 9))  # fresh shape: forces a compile this fit
    rep = distributed_pca_fit(x, 3, data_mesh()).fit_report_
    assert rep.compiles >= 1
    assert rep.compile_seconds > 0
    assert rep.programs_compiled >= rep.compiles
    assert rep.phases["execute"] > 0


def test_eager_solve_is_counted_where_tracked_jit_is_blind(rng):
    """``compiles`` sees only ``tracked_jit`` functions; the randomized
    solve runs eagerly, one small program per op. The ``jax.monitoring``
    listener counts those too — and an identical second fit builds none."""
    from spark_rapids_ml_tpu import PCA

    def chunks():  # a shape no other test fits, so the first fit compiles
        return iter([rng.normal(size=(37, 29)).astype(np.float32)] * 2)

    def fit():
        return PCA().setK(3).set("svdSolver", "randomized").fit(
            chunks()).fit_report_

    first, second = fit(), fit()
    # (fetched, not compiled, where a persistent cache already holds them)
    built = first.programs_compiled + first.programs_fetched
    assert built > 1 and built > first.compiles
    assert first.programs_compiled > 1 or first.programs_fetched > 1
    assert second.programs_compiled == 0 and second.programs_fetched == 0


def test_forgotten_fallbacks_are_counted_again_only_when_they_recur():
    """``fallback_signatures()`` is process-wide; ``forget_fallback_signatures``
    lets a reader that shares its process count from now on."""
    from spark_rapids_ml_tpu.obs import xprof

    class NoAot:
        """A jitted function whose AOT path is not there."""

        def __init__(self, jitted):
            self._jitted = jitted

        def lower(self, *args, **kwargs):
            raise RuntimeError("no ahead-of-time compile here")

        def __call__(self, *args, **kwargs):
            return self._jitted(*args, **kwargs)

    @tracked_jit(label="xprof_falls_back")
    def f(x):
        return x + 1.0

    f._jitted = NoAot(f._jitted)
    a = jnp.ones((3,))
    np.testing.assert_allclose(np.asarray(f(a)), 2.0)  # still answers
    assert xprof.fallback_signatures()["xprof_falls_back"] == 1
    xprof.forget_fallback_signatures()
    assert xprof.fallback_signatures() == {}
    assert f.stats()["signatures"] == 0  # only the fallen signature went
    np.testing.assert_allclose(np.asarray(f(a)), 2.0)  # tries again
    assert xprof.fallback_signatures() == {"xprof_falls_back": 1}
    xprof.forget_fallback_signatures()
