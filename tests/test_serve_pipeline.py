"""Pipelined batcher correctness (PR 9): async results bit-equal to the
synchronous path across ragged sizes, padded rows never leak through the
in-flight window, a batch failure mid-window fails only its own members,
donation never aliases a buffer a retry still holds (fault raise + retry
under the pipelined loop), submit-time dtype coercion, the
stage/dispatch/sync phase split + overlap metrics, reduced-precision
variants (env-gated, separate signatures, max-error-guarded), the
StagingPool rotation contract, wedge recovery with batches in flight,
and the rule-9 static check."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu.obs import get_registry
from spark_rapids_ml_tpu.obs.serving import last_transform_report
from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine
from spark_rapids_ml_tpu.serve.batching import (
    AsyncTransformSpec,
    MicroBatcher,
    WorkerCrashed,
)
from spark_rapids_ml_tpu.serve.faults import fault_plane, reset_fault_plane
from spark_rapids_ml_tpu.utils.padding import StagingPool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    reset_fault_plane()
    yield
    reset_fault_plane()


@pytest.fixture
def pca_model(rng):
    from spark_rapids_ml_tpu import PCA

    x = rng.normal(size=(256, 16))
    return PCA().setK(4).fit(x), x


def _metric(name, **labels):
    snap = get_registry().snapshot().get(name, {"samples": []})
    for s in snap["samples"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


# -- bit-equality through the pipeline --------------------------------------


def test_pipeline_bit_equal_ragged_sizes_f64(pca_model):
    """Ragged request sizes inside one bucket, depth-2 window: every
    response bit-equal to the blocking direct transform (same XLA
    module), padding never visible."""
    model, x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_pca", model, buckets=(32, 64))
    engine = ServeEngine(reg, max_batch_rows=64, max_wait_ms=2,
                         buckets=(32, 64), pipeline_depth=2)
    try:
        sizes = [1, 3, 7, 12, 19, 25, 31, 17, 5, 29]
        outs = {}
        errors = []

        def worker(i):
            try:
                outs[i] = engine.predict("pipe_pca", x[i:i + sizes[i]])
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(sizes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for i, n in enumerate(sizes):
            direct = np.asarray(
                model.transform(x[i:i + n]).column("pca_features"))
            assert outs[i].shape == direct.shape  # no padding leaked
            np.testing.assert_array_equal(outs[i], direct)
    finally:
        engine.shutdown()


def test_pipeline_bit_equal_f32_model(rng):
    """An f32 model through the pipeline: submit coerces once to f32
    (not the old f64 blanket), outputs still bit-equal to the sync
    path."""
    from spark_rapids_ml_tpu import PCA

    x = rng.normal(size=(128, 8))
    model = PCA().setK(3).setDtype("float32").fit(x)
    reg = ModelRegistry()
    reg.register("pipe_pca32", model, buckets=(16, 32))
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1,
                         buckets=(16, 32), pipeline_depth=2)
    try:
        out = engine.predict("pipe_pca32", x[:11])
        direct = np.asarray(
            model.transform(x[:11]).column("pca_features"))
        np.testing.assert_array_equal(out, direct)
        batcher = next(iter(engine._batchers.values()))
        assert batcher.dtype == np.float32
    finally:
        engine.shutdown()


def test_pipeline_depth_one_is_the_sync_kill_switch(pca_model):
    """PIPELINE_DEPTH=1 at native precision restores the blocking path:
    no async spec, f64 staging dtype, identical outputs."""
    model, x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_kill", model, buckets=(32,))
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1,
                         buckets=(32,), pipeline_depth=1)
    try:
        out = engine.predict("pipe_kill", x[:9])
        direct = np.asarray(
            model.transform(x[:9]).column("pca_features"))
        np.testing.assert_array_equal(out, direct)
        batcher = next(iter(engine._batchers.values()))
        assert batcher.async_spec is None
        assert batcher.pipeline_depth == 1
        assert batcher.dtype == np.float64
    finally:
        engine.shutdown()


# -- dtype coercion at the door ---------------------------------------------


def test_submit_skips_copy_when_dtype_matches():
    b = MicroBatcher(lambda m: m, name="dtype_skip", max_batch_rows=8,
                     max_wait_ms=1, dtype=np.float32)
    try:
        rows32 = np.ones((2, 3), dtype=np.float32)
        req = b.submit(rows32)
        assert req.rows is rows32  # np.asarray no-op: zero copy bytes
        assert req.wait(5.0).shape == (2, 3)
        rows64 = np.ones((2, 3), dtype=np.float64)
        req = b.submit(rows64)
        assert req.rows.dtype == np.float32  # coerced ONCE, at the door
    finally:
        b.close()


# -- mid-window failure isolation -------------------------------------------


def _spec(dispatch, dtype=np.float64, algo="pipe_test"):
    return AsyncTransformSpec(
        stage=lambda m: m, dispatch=dispatch,
        complete=lambda h: h, dtype=dtype, algo=algo,
    )


def test_batch_failure_mid_window_fails_only_its_members():
    """Three full batches through a depth-2 window; the second one's
    dispatch raises. Only its members see the error — the first and
    third batches complete with their own rows."""
    calls = {"n": 0}

    def dispatch(m):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom on batch 2")
        return m * 2.0

    b = MicroBatcher(lambda m: m, name="midwindow", max_batch_rows=8,
                     max_wait_ms=1, async_spec=_spec(dispatch),
                     pipeline_depth=2)
    try:
        reqs = []
        for i in range(3):
            # full batches: 8 rows hits the cap, no linger, one batch per
            # submit — deterministic batch boundaries
            reqs.append(b.submit(np.full((8, 2), float(i))))
            time.sleep(0.05)
        r0 = reqs[0].wait(5.0)
        np.testing.assert_array_equal(r0, np.zeros((8, 2)))
        with pytest.raises(RuntimeError, match="boom on batch 2"):
            reqs[1].wait(5.0)
        r2 = reqs[2].wait(5.0)
        np.testing.assert_array_equal(r2, np.full((8, 2), 4.0))
        assert _metric("sparkml_serve_errors_total", model="midwindow",
                       error="RuntimeError") == 1
    finally:
        b.close()


def test_retry_after_fault_gets_correct_rows_under_pipeline(pca_model):
    """Donation never aliases a buffer a retry still holds: the retry
    path re-enters submit with the caller's host rows and stages a FRESH
    buffer, so a raise + retry under the pipelined loop still returns
    bit-equal results."""
    model, x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_retry", model, buckets=(32,))
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1,
                         buckets=(32,), pipeline_depth=2,
                         retries=2, backoff_ms=1)
    try:
        engine.warmup("pipe_retry")
        fault_plane().inject("pipe_retry", "raise", count=1)
        result = engine.predict_detailed("pipe_retry", x[:13])
        assert result.retries == 1
        direct = np.asarray(
            model.transform(x[:13]).column("pca_features"))
        np.testing.assert_array_equal(result.outputs, direct)
    finally:
        engine.shutdown()


# -- pipeline telemetry ------------------------------------------------------


def test_pipeline_phase_split_and_overlap_metrics(pca_model):
    model, x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_obs", model, buckets=(32, 64))
    engine = ServeEngine(reg, max_batch_rows=64, max_wait_ms=1,
                         buckets=(32, 64), pipeline_depth=2)
    try:
        threads = [
            threading.Thread(
                target=lambda i=i: engine.predict(
                    "pipe_obs", x[i:i + 5 + i]))
            for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        report = last_transform_report("pca")
        assert report.extra.get("pipelined") is True
        for phase in ("stage", "dispatch", "sync", "total"):
            assert phase in report.phases
        busy = _metric("sparkml_serve_device_busy_seconds_total",
                       model="pipe_obs")
        assert busy is not None and busy > 0
        assert _metric("sparkml_serve_pipeline_overlap_seconds_total",
                       model="pipe_obs") is not None
        # window fully drained after the burst
        assert _metric("sparkml_serve_pipeline_inflight",
                       model="pipe_obs") == 0
    finally:
        engine.shutdown()


# -- reduced precision -------------------------------------------------------


def test_precision_off_by_default(pca_model):
    model, _x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_prec0", model)
    engine = ServeEngine(reg, pipeline_depth=2)
    try:
        assert engine.precision == "native"
        spec = engine._async_spec_for(reg.resolve_entry("pipe_prec0"))
        assert spec is not None and spec.precision == "native"
    finally:
        engine.shutdown()


def test_bf16_and_int8_ladders_are_separate_signatures(pca_model):
    """Reduced-precision variants compile their own tracked signatures
    per bucket and land within the max-error bar of the native path."""
    from spark_rapids_ml_tpu.obs.xprof import signature_count

    model, x = pca_model
    direct = np.asarray(model.transform(x[:20]).column("pca_features"))
    scale = np.max(np.abs(direct))
    for precision, tol in (("bf16", 0.05), ("int8", 0.05)):
        reg = ModelRegistry()
        reg.register(f"pipe_{precision}", model, buckets=(32, 64))
        engine = ServeEngine(reg, max_batch_rows=64, max_wait_ms=1,
                             buckets=(32, 64), pipeline_depth=2,
                             precision=precision)
        try:
            label = f"pca_transform_{precision}"
            before = signature_count(label)
            engine.warmup(f"pipe_{precision}")
            after = signature_count(label)
            assert after - before >= 2  # one per bucket
            out = engine.predict(f"pipe_{precision}", x[:20])
            err = np.max(np.abs(out - direct)) / scale
            assert err <= tol
            assert err > 0  # genuinely reduced precision, not native
        finally:
            engine.shutdown()


def test_precision_guard_falls_back_to_native(pca_model):
    """An impossible max-error bar fails the offline check: the engine
    counts the fallback and serves bit-equal native outputs."""
    model, x = pca_model
    reg = ModelRegistry()
    reg.register("pipe_guard", model, buckets=(32,))
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1,
                         buckets=(32,), pipeline_depth=2,
                         precision="int8")
    try:
        engine.precision_max_err = 0.0  # nothing quantized can pass
        out = engine.predict("pipe_guard", x[:9])
        direct = np.asarray(
            model.transform(x[:9]).column("pca_features"))
        np.testing.assert_array_equal(out, direct)
        assert _metric("sparkml_serve_precision_fallback_total",
                       model="pipe_guard", precision="int8") == 1
        assert _metric("sparkml_serve_precision_checks_total",
                       model="pipe_guard", precision="int8",
                       verdict="fail") == 1
    finally:
        engine.shutdown()


def test_kmeans_and_logreg_serving_programs(rng):
    """The other two serving programs agree with their sync paths."""
    from spark_rapids_ml_tpu.models.kmeans import KMeans
    from spark_rapids_ml_tpu.models.logistic_regression import (
        LogisticRegression,
    )

    x = rng.normal(size=(200, 8))
    km = KMeans().setK(3).fit(x)
    reg = ModelRegistry()
    reg.register("pipe_km", km, buckets=(16, 32))
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1,
                         buckets=(16, 32), pipeline_depth=2)
    try:
        out = engine.predict("pipe_km", x[:13])
        direct = np.asarray(km.transform(x[:13]).column("prediction"))
        np.testing.assert_array_equal(out, direct)
    finally:
        engine.shutdown()

    # noisy labels + L2: perfectly separable data would diverge the
    # unregularized Newton fit (coefficients → inf → NaN)
    y = (x[:, 0] + 0.3 * x[:, 1] + 0.5 * rng.normal(size=200)
         > 0).astype(np.float64)
    from spark_rapids_ml_tpu.data.frame import VectorFrame

    frame = VectorFrame({"features": list(x), "label": y})
    lr = LogisticRegression().setRegParam(0.1).fit(frame)
    reg2 = ModelRegistry()
    reg2.register("pipe_lr", lr, buckets=(16, 32))
    engine2 = ServeEngine(reg2, max_batch_rows=32, max_wait_ms=1,
                          buckets=(16, 32), pipeline_depth=2)
    try:
        out = engine2.predict("pipe_lr", x[:13])
        direct = np.asarray(lr.predict_proba(x[:13]))
        np.testing.assert_array_equal(out, direct)
    finally:
        engine2.shutdown()


# -- staging pool ------------------------------------------------------------


def test_staging_pool_rotation_and_tail_zeroing():
    pool = StagingPool(np.float64, slots=2)
    a, n = pool.fill([np.ones((5, 3))], buckets=(8,))
    assert (a.shape, n) == ((8, 3), 5)
    assert np.all(a[:5] == 1.0) and np.all(a[5:] == 0.0)
    # second fill rotates to a different buffer
    b, _ = pool.fill([np.full((6, 3), 2.0)], buckets=(8,))
    assert b is not a
    assert np.all(b[:6] == 2.0) and np.all(b[6:] == 0.0)
    # third fill reuses the first buffer AND re-zeroes the stale tail
    c, _ = pool.fill([np.full((2, 3), 3.0)], buckets=(8,))
    assert c is a
    assert np.all(c[:2] == 3.0) and np.all(c[2:] == 0.0)


def test_staging_pool_exact_fit_is_zero_copy():
    pool = StagingPool(np.float64, slots=2)
    exact = np.ones((8, 3))
    staged, n = pool.fill([exact], buckets=(8,))
    assert staged is exact and n == 8
    # multi-part batches always stage (the concat must happen somewhere)
    staged, n = pool.fill([np.ones((4, 3)), np.ones((4, 3))],
                          buckets=(8,))
    assert staged is not exact and n == 8


def test_staging_pool_rejects_width_mismatch():
    """A width-1 request behind a wide one must FAIL the batch loudly
    (as np.concatenate did), never NumPy-broadcast a single column
    across every feature and serve plausible-looking garbage."""
    pool = StagingPool(np.float64, slots=2)
    with pytest.raises(ValueError, match="feature"):
        pool.fill([np.ones((3, 64)), np.ones((5, 1))], buckets=(16,))


def test_staging_pool_coerces_dtype():
    pool = StagingPool(np.float32, slots=2)
    staged, n = pool.fill([np.ones((3, 2), dtype=np.float64)],
                          buckets=(4,))
    assert staged.dtype == np.float32 and n == 3


# -- wedge recovery with batches in flight ----------------------------------


def test_wedge_mid_window_fails_window_and_restarts(tmp_path, monkeypatch):
    """A dispatch that stalls past the worker budget with a depth-2
    window: every in-flight request fails fast with WorkerCrashed, the
    replacement worker serves new traffic — no stuck window."""
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_DUMP_DIR", str(tmp_path))
    stall = {"armed": True}

    def dispatch(m):
        if stall["armed"]:
            stall["armed"] = False
            time.sleep(1.5)
        return m

    b = MicroBatcher(lambda m: m, name="pipe_wedge", max_batch_rows=8,
                     max_wait_ms=1, async_spec=_spec(dispatch),
                     pipeline_depth=2, worker_budget_s=0.2)
    try:
        r1 = b.submit(np.ones((8, 2)))
        time.sleep(0.05)
        r2 = b.submit(np.ones((8, 2)) * 2)
        with pytest.raises(WorkerCrashed):
            r1.wait(5.0)
        # r2 either rode the failed window or was still queued and got
        # served by the replacement — both are terminal outcomes, fast
        try:
            out = r2.wait(5.0)
            np.testing.assert_array_equal(out, np.ones((8, 2)) * 2)
        except WorkerCrashed:
            pass
        # the replacement worker serves fresh traffic (no stuck window)
        r3 = b.submit(np.full((8, 2), 3.0))
        np.testing.assert_array_equal(r3.wait(5.0), np.full((8, 2), 3.0))
        assert _metric("sparkml_serve_worker_restarts_total",
                       model="pipe_wedge") == 1
        # stranded entries flushed their busy intervals: the occupancy
        # accounting is not left elevated by the abandoned window
        assert _metric("sparkml_serve_pipeline_inflight",
                       model="pipe_wedge") == 0
    finally:
        b.close()


def test_wedge_inside_stage_step_is_detected(tmp_path, monkeypatch):
    """The device backend hangs INSIDE the host→device
    transfer (the stage step). The watchdog is armed before staging, so
    the hang is budget-detected — requests fail fast with WorkerCrashed
    and a replacement worker takes over, instead of the worker blocking
    forever with no restart and no dump."""
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_DUMP_DIR", str(tmp_path))
    stall = {"armed": True}

    def stage(m):
        if stall["armed"]:
            stall["armed"] = False
            time.sleep(1.5)  # wedged device_put
        return m

    spec = AsyncTransformSpec(stage=stage, dispatch=lambda h: h,
                              complete=lambda h: h, dtype=np.float64,
                              algo="pipe_stage_wedge")
    b = MicroBatcher(lambda m: m, name="pipe_stage_wedge",
                     max_batch_rows=8, max_wait_ms=1, async_spec=spec,
                     pipeline_depth=2, worker_budget_s=0.2)
    try:
        r1 = b.submit(np.ones((8, 2)))
        with pytest.raises(WorkerCrashed):
            r1.wait(5.0)
        r2 = b.submit(np.full((8, 2), 2.0))
        np.testing.assert_array_equal(r2.wait(5.0), np.full((8, 2), 2.0))
        assert _metric("sparkml_serve_worker_restarts_total",
                       model="pipe_stage_wedge") == 1
        assert _metric("sparkml_serve_pipeline_inflight",
                       model="pipe_stage_wedge") == 0
    finally:
        b.close()


# -- rule 9 ------------------------------------------------------------------


def test_rule9_accepts_current_batching():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_instrumentation as ci
    finally:
        sys.path.pop(0)
    assert list(ci.check_pipeline_sync(ci.BATCHING_FILE)) == []


def test_rule9_rejects_stray_host_sync(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_instrumentation as ci
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad_batching.py"
    bad.write_text(
        "import numpy as np\n"
        "class MicroBatcher:\n"
        "    def submit(self, rows):\n"
        "        return np.asarray(rows)  # allowed: the door\n"
        "    def _complete_batch(self, entry):\n"
        "        return np.asarray(entry)  # allowed: THE sync\n"
        "    def _stage_dispatch(self, batch):\n"
        "        x = np.asarray(batch)  # REJECT: sync in the stage step\n"
        "        x.block_until_ready()  # REJECT\n"
        "        return x\n"
    )
    offenders = list(ci.check_pipeline_sync(str(bad)))
    assert len(offenders) == 2
    assert all("completion step" in why for _ln, why in offenders)
