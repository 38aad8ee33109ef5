"""obs.fitmon: step/run lifecycle under injected clocks (zero cadence
sleeps), MFU/roofline math against hand-computed fixtures, the
unknown-device-kind degradation contract (absent, never fake), straggler
detection, the backend watchdog's platform-mismatch and wedged-canary
verdicts each driving exactly one auto-resolving ``fit_backend_degraded``
incident through the real detector pipeline, disabled-monitor inertness,
the ``/debug/fit`` document shape, and StreamingTrainer folds landing in
the monitor's run history."""

import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu.obs import fitmon
from spark_rapids_ml_tpu.obs import flight
from spark_rapids_ml_tpu.obs.anomaly import ThresholdDetector
from spark_rapids_ml_tpu.obs.fitmon import (
    BACKEND_OK_METRIC,
    INCIDENT_NAME,
    BackendWatchdog,
    FitMonitor,
    detect_stragglers,
    device_peaks,
    roofline_bound,
    step_mfu,
)
from spark_rapids_ml_tpu.obs.incidents import IncidentEngine, IncidentManager
from spark_rapids_ml_tpu.obs.metrics import MetricsRegistry, get_registry
from spark_rapids_ml_tpu.obs.tsdb import MetricsSampler, TimeSeriesStore

PEAK_FLOPS = 1.0e12
PEAK_BW = 1.0e11


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t


class FakeDevice:
    def __init__(self, platform="cpu", device_kind="host", n=1):
        self.platform = platform
        self.device_kind = device_kind


def _monitor(clock=None, enabled=True, peaks=(PEAK_FLOPS, PEAK_BW),
             watchdog=None):
    return FitMonitor(
        enabled=enabled,
        clock=clock if clock is not None else FakeClock(),
        peaks_fn=lambda: peaks,
        watchdog=watchdog if watchdog is not None else _watchdog(),
    )


def _watchdog(**kw):
    kw.setdefault("expected_platform", None)
    kw.setdefault("interval_s", 30.0)
    kw.setdefault("clock", FakeClock())
    kw.setdefault("devices_fn", lambda: [FakeDevice()])
    kw.setdefault("canary_fn", lambda: None)
    return BackendWatchdog(**kw)


# -- pure math fixtures -------------------------------------------------------


def test_step_mfu_hand_computed():
    # 1e12 FLOPs over 2 s of device time on a 1e12 FLOP/s chip = 50%
    assert step_mfu(1.0e12, 2.0, PEAK_FLOPS) == pytest.approx(0.5)
    assert step_mfu(5.0e11, 1.0, PEAK_FLOPS) == pytest.approx(0.5)
    # any unknown input → None, never a fake number
    assert step_mfu(None, 2.0, PEAK_FLOPS) is None
    assert step_mfu(1.0e12, None, PEAK_FLOPS) is None
    assert step_mfu(1.0e12, 0.0, PEAK_FLOPS) is None
    assert step_mfu(1.0e12, 2.0, None) is None
    assert step_mfu(0.0, 2.0, PEAK_FLOPS) is None


def test_roofline_bound_vs_ridge_point():
    # ridge = 1e12 / 1e11 = 10 FLOPs/byte
    # intensity 1000 >> ridge → compute-bound
    assert roofline_bound(1.0e9, 1.0e6, PEAK_FLOPS, PEAK_BW) == "compute"
    # intensity 1 << ridge → memory-bound
    assert roofline_bound(1.0e6, 1.0e6, PEAK_FLOPS, PEAK_BW) == "memory"
    # exactly at the ridge counts as compute-bound
    assert roofline_bound(10.0, 1.0, PEAK_FLOPS, PEAK_BW) == "compute"
    for args in [(None, 1.0e6, PEAK_FLOPS, PEAK_BW),
                 (1.0e6, None, PEAK_FLOPS, PEAK_BW),
                 (1.0e6, 1.0e6, None, PEAK_BW),
                 (1.0e6, 1.0e6, PEAK_FLOPS, None)]:
        assert roofline_bound(*args) is None


def test_detect_stragglers_synthetic_timings():
    verdict = detect_stragglers(
        {"host0": 0.10, "host1": 0.11, "host2": 0.45}, ratio=1.5)
    assert verdict["stragglers"] == ["host2"]
    assert verdict["median_seconds"] == pytest.approx(0.11)
    # strictly above ratio*median: a host AT the bar is not flagged
    at_bar = detect_stragglers({"a": 1.0, "b": 1.0, "c": 1.5}, ratio=1.5)
    assert at_bar["stragglers"] == []
    # fewer than two hosts: no median to diverge from, never flagged
    assert detect_stragglers({"only": 99.0})["stragglers"] == []
    assert detect_stragglers({})["stragglers"] == []
    assert detect_stragglers({})["median_seconds"] is None


def test_device_peaks_env_override_and_unknown_kind(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_FITMON_PEAK_FLOPS", "2.5e13")
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_FITMON_PEAK_BW", "8e11")
    assert device_peaks() == (2.5e13, 8.0e11)
    # malformed override falls through to the table; this process runs
    # on CPU (an unlisted kind) → (None, None), not a guess
    monkeypatch.setenv("SPARK_RAPIDS_ML_TPU_FITMON_PEAK_FLOPS", "fast")
    monkeypatch.delenv("SPARK_RAPIDS_ML_TPU_FITMON_PEAK_BW")
    assert device_peaks() == (None, None)
    monkeypatch.delenv("SPARK_RAPIDS_ML_TPU_FITMON_PEAK_FLOPS")
    assert device_peaks() == (None, None)


# -- step / run lifecycle (injected clocks, zero sleeps) ----------------------


def test_run_lifecycle_steps_totals_and_history():
    clock = FakeClock(1000.0)
    monitor = _monitor(clock=clock)
    run = monitor.start_run("distributed_pca", trace_id="tr-1")
    assert run.active and run.run_id == "fit-1"
    assert monitor.active_runs() == [run]
    assert monitor.latest_active_run_id() == "fit-1"

    with run.step("gram", rows=4096) as mon:
        run.record_program("gram", 1.0e12, 1.0e8)
        mon.set_device_seconds(2.0)
        mon.note(n_iter=3, cost=0.125, junk="not-a-number")
    clock.t = 1010.0
    with run.step("eigh") as mon:
        mon.set_device_seconds(0.5)

    (gram, eigh) = list(run.steps)
    assert gram["step"] == "gram" and gram["index"] == 0
    assert gram["rows"] == 4096
    assert gram["device_seconds"] == pytest.approx(2.0)
    assert gram["flops"] == pytest.approx(1.0e12)
    # MFU from the injected peak: 1e12 FLOPs / 2 s / 1e12 peak = 0.5
    assert gram["mfu"] == pytest.approx(0.5)
    # intensity 1e12/1e8 = 1e4 >> ridge 10 → compute-bound
    assert gram["bound"] == "compute"
    assert gram["rows_per_sec"] is not None and gram["rows_per_sec"] > 0
    assert gram["scalars"] == {"n_iter": 3.0, "cost": 0.125}
    assert eigh["rows"] is None and eigh["rows_per_sec"] is None
    # program cost landed in the FIRST step only (delta attribution)
    assert eigh["flops"] is None and eigh["mfu"] is None

    summary = run.summary()
    assert summary["steps"] == 2 and summary["steps_failed"] == 0
    assert summary["rows"] == 4096
    assert summary["device_seconds"] == pytest.approx(2.5)
    assert summary["started_unix"] == 1000.0
    assert summary["last_scalars"] == {}  # eigh noted nothing

    clock.t = 1020.0
    monitor.finish_run(run, report={"k": 3})
    assert not run.active and run.finished_unix == 1020.0
    assert monitor.active_runs() == []
    assert monitor.recent_runs() == [run]
    assert monitor.find_run("fit-1") is run
    assert run.as_dict()["report"] == {"k": 3}


def test_failed_step_counted_and_run_survives():
    monitor = _monitor()
    run = monitor.start_run("distributed_kmeans")
    with pytest.raises(RuntimeError):
        with run.step("lloyd", rows=128):
            raise RuntimeError("kernel blew up")
    assert run.steps_total == 1 and run.steps_failed == 1
    assert list(run.steps)[0]["failed"] is True


def test_fit_run_context_and_current_run(monkeypatch):
    monitor = _monitor()
    monkeypatch.setattr(fitmon, "_monitor", monitor)
    assert fitmon.current_run() is fitmon._NULL_RUN
    with fitmon.fit_run("distributed_pca") as run:
        assert fitmon.current_run() is run
        with run.step("power_iter", rows=64) as mon:
            mon.set_device_seconds(0.25)
    # exiting the context finished the run and restored the null run
    assert fitmon.current_run() is fitmon._NULL_RUN
    (done,) = monitor.recent_runs()
    assert done.algo == "distributed_pca" and not done.active


def test_step_metrics_published_to_registry():
    reg = get_registry()
    monitor = _monitor()
    run = monitor.start_run("distributed_pca")
    with run.step("gram", rows=100) as mon:
        run.record_program("gram", 1.0e12, 1.0e8)
        mon.set_device_seconds(2.0)
    monitor.finish_run(run)
    counter = reg.counter("sparkml_fit_device_seconds_total", "",
                          ("algo", "step"))
    assert counter.value(algo="distributed_pca",
                         step="gram") >= 2.0
    gauge = reg.gauge("sparkml_fit_mfu", "", ("algo", "step"))
    assert gauge.value(algo="distributed_pca",
                       step="gram") == pytest.approx(0.5)


def test_unknown_device_kind_degrades_to_absent_mfu():
    reg = MetricsRegistry()
    monitor = _monitor(peaks=(None, None))
    run = monitor.start_run("distributed_glm")
    with run.step("irls", rows=256) as mon:
        run.record_program("irls", 1.0e12, 1.0e8)
        mon.set_device_seconds(1.0)
    (step,) = list(run.steps)
    # FLOPs are known but the chip peak is not: MFU and the roofline
    # verdict are ABSENT, never fabricated from a guessed peak
    assert step["flops"] == pytest.approx(1.0e12)
    assert step["mfu"] is None and step["bound"] is None
    assert run.summary()["mfu_mean"] is None
    doc = monitor.debug_doc()
    assert doc["peaks"] == {"flops_per_second": None,
                            "hbm_bytes_per_second": None}
    del reg  # registry only to keep the fixture idiom obvious


def test_straggler_detection_via_run_skew():
    monitor = _monitor()
    run = monitor.start_run("distributed_kmeans")
    for _ in range(4):
        run.note_host_step("host0", 0.10)
        run.note_host_step("host1", 0.11)
        run.note_host_step("host2", 0.45)
    skew = run.skew()
    assert skew["stragglers"] == ["host2"]
    assert skew["median_seconds"] == pytest.approx(0.11)
    assert run.summary()["stragglers"] == ["host2"]
    # the per-host seconds also land on the labelled counter
    assert get_registry().counter(
        "sparkml_fit_host_step_seconds_total", "", ("algo", "host"),
    ).value(algo="distributed_kmeans", host="host2") >= 4 * 0.45


def test_collectives_ledger_in_run_dict():
    monitor = _monitor()
    run = monitor.start_run("distributed_pca")
    run.record_collective("psum", nbytes=1024, count=3, seconds=0.01)
    run.record_collective("psum", nbytes=1024)
    doc = run.as_dict()["collectives"]["psum"]
    assert doc["count"] == 4
    assert doc["bytes"] == 4 * 1024
    assert doc["seconds"] == pytest.approx(0.01)


# -- disabled monitor: inert, zero-allocation null path -----------------------


def test_disabled_monitor_is_inert(monkeypatch):
    monitor = _monitor(enabled=False)
    monkeypatch.setattr(fitmon, "_monitor", monitor)
    with fitmon.fit_run("distributed_pca") as run:
        assert run is fitmon._NULL_RUN
        step = run.step("gram", rows=10)
        assert step is fitmon._NULL_STEP
        with step as mon:
            mon.note(cost=1.0)
            mon.set_device_seconds(5.0)
        run.note_host_step("h", 1.0)
        run.record_collective("psum", nbytes=8)
    assert monitor.active_runs() == []
    assert monitor.recent_runs() == []
    assert run.summary() == {} and run.as_dict() == {}
    # a run started while enabled stops recording once disabled
    monitor.enabled = True
    live = monitor.start_run("distributed_pca")
    monitor.enabled = False
    assert live.step("gram") is fitmon._NULL_STEP
    assert live.steps_total == 0


# -- the backend watchdog -----------------------------------------------------


def test_watchdog_cadence_bounded_by_interval():
    clock = FakeClock(1000.0)
    wd = _watchdog(clock=clock, interval_s=30.0)
    first = wd.maybe_check()
    assert first["ok"] is True and wd.checks == 1
    clock.t = 1010.0  # inside the interval: cached verdict, no re-check
    cached = wd.maybe_check()
    assert cached["checked_unix"] == 1000.0 and wd.checks == 1
    clock.t = 1031.0
    fresh = wd.maybe_check()
    assert fresh["checked_unix"] == 1031.0 and wd.checks == 2


def test_watchdog_verdicts_mismatch_no_devices_canary_error():
    wd = _watchdog(expected_platform="tpu",
                   devices_fn=lambda: [FakeDevice(platform="cpu")])
    verdict = wd.check()
    assert verdict["ok"] is False
    assert verdict["reason"] == "platform_mismatch"
    assert verdict["platform"] == "cpu"
    assert verdict["expected_platform"] == "tpu"

    empty = _watchdog(devices_fn=lambda: [])
    assert empty.check()["reason"] == "no_devices"

    def _boom():
        raise RuntimeError("dispatch failed")

    broken = _watchdog(canary_fn=_boom)
    verdict = broken.check()
    assert verdict["reason"] == "canary_error"
    assert "dispatch failed" in verdict["canary_error"]


def _incident_pipeline(tmp_path, monkeypatch):
    """The REAL detection pipeline the serve server runs: watchdog gauge
    → sampler snapshot → builtin-shaped ThresholdDetector → engine →
    manager hysteresis, all under injected timestamps."""
    monkeypatch.setenv(flight.DUMP_DIR_ENV, str(tmp_path / "dumps"))
    clock = FakeClock(1000.0)
    store = TimeSeriesStore(tiers=((1.0, 600.0),), clock=clock)
    sampler = MetricsSampler(store, registry=get_registry(),
                             interval_seconds=1.0, clock=clock)
    reg = MetricsRegistry()
    engine = IncidentEngine(
        store=store,
        detectors=[ThresholdDetector(
            INCIDENT_NAME, BACKEND_OK_METRIC,
            threshold=0.5, direction="<",
            kind="backend", severity="critical")],
        manager=IncidentManager(open_after=1, resolve_after=2,
                                cooldown_seconds=0.0, capture_seconds=0.0,
                                registry=reg),
        registry=reg,
    )

    def tick(wd):
        wd.check(now=clock.t)
        sampler.sample_once(now=clock.t)
        opened = engine.sweep(now=clock.t)
        clock.t += 1.0
        return opened

    return engine, tick


def test_platform_mismatch_exactly_one_auto_resolving_incident(
        tmp_path, monkeypatch):
    engine, tick = _incident_pipeline(tmp_path, monkeypatch)
    wd = _watchdog(expected_platform="tpu",
                   devices_fn=lambda: [FakeDevice(platform="cpu")])
    opened = tick(wd)
    assert len(opened) == 1
    assert opened[0].detector == INCIDENT_NAME
    assert opened[0].severity == "critical"
    # the degraded state persists: the SAME incident updates, no dupes
    for _ in range(4):
        assert tick(wd) == []
    assert engine.manager.opened_total == 1
    # the operator fixes the expectation; the gauge recovers and the
    # incident auto-resolves after the quiet hysteresis
    wd.expected_platform = None
    tick(wd)
    tick(wd)
    assert engine.manager.open_incidents() == []
    (recent,) = engine.manager.recent_incidents()
    assert recent["detector"] == INCIDENT_NAME
    assert recent["state"] == "resolved"
    assert engine.manager.resolved_total == 1


def test_wedged_canary_exactly_one_auto_resolving_incident(
        tmp_path, monkeypatch):
    engine, tick = _incident_pipeline(tmp_path, monkeypatch)
    release = threading.Event()
    wedged = {"on": True}

    def canary():
        if wedged["on"]:
            release.wait(5.0)  # a hung device backend: never returns

    wd = _watchdog(canary_fn=canary, canary_timeout_s=0.01)
    try:
        opened = tick(wd)
        assert len(opened) == 1
        assert opened[0].detector == INCIDENT_NAME
        assert wd.last_verdict()["reason"] == "canary_wedged"
        assert tick(wd) == []  # still wedged: update, not a duplicate
        assert engine.manager.opened_total == 1
        wedged["on"] = False  # the backend recovers
        tick(wd)
        tick(wd)
        assert engine.manager.open_incidents() == []
        (recent,) = engine.manager.recent_incidents()
        assert recent["state"] == "resolved"
    finally:
        release.set()


# -- /debug/fit ---------------------------------------------------------------


def test_debug_fit_doc_shape(monkeypatch):
    monitor = _monitor()
    monkeypatch.setattr(fitmon, "_monitor", monitor)
    run = monitor.start_run("distributed_pca")
    with run.step("gram", rows=32) as mon:
        mon.set_device_seconds(0.1)
    monitor.finish_run(run)
    active = monitor.start_run("distributed_kmeans")
    with active.step("lloyd", rows=64) as mon:
        mon.set_device_seconds(0.2)
    monitor.watchdog.check()

    doc = fitmon.debug_fit_doc()
    assert set(doc) == {"enabled", "active", "recent", "rollup",
                        "watchdog", "straggler_ratio", "peaks"}
    assert doc["enabled"] is True
    (act,) = doc["active"]
    assert act["run_id"] == active.run_id
    assert "step_table" in act and "skew" in act
    (rec,) = doc["recent"]
    assert rec["run_id"] == run.run_id and "step_table" not in rec
    rollup = doc["rollup"]
    assert rollup["distributed_pca"]["runs"] == 1
    assert rollup["distributed_kmeans"]["active"] == 1
    assert rollup["distributed_pca"]["device_seconds"] == \
        pytest.approx(0.1)
    assert doc["watchdog"]["ok"] is True
    assert doc["peaks"] == {"flops_per_second": PEAK_FLOPS,
                            "hbm_bytes_per_second": PEAK_BW}
    report = fitmon.fit_report()
    assert report["enabled"] is True
    assert set(report["algos"]) == {"distributed_pca",
                                    "distributed_kmeans"}


# -- StreamingTrainer folds in run history ------------------------------------


def test_streaming_trainer_folds_visible_in_run_history(
        tmp_path, monkeypatch, rng):
    from spark_rapids_ml_tpu.serve import ModelRegistry, StreamingTrainer

    monitor = _monitor()
    monkeypatch.setattr(fitmon, "_monitor", monitor)
    reg = ModelRegistry()
    trainer = StreamingTrainer(
        reg, "fitmon_pca", 8, 2,
        batches_per_version=2, artifact_dir=str(tmp_path))
    data = rng.normal(size=(512, 8))
    trainer.feed(data[:128])
    # mid-cycle: the publish cycle's FitRun is active and holds the fold
    (active,) = monitor.active_runs()
    assert active.algo == "streaming_trainer:fitmon_pca"
    version = trainer.feed(data[128:256])
    assert version == 1
    # publishing closed the run with the version-stream report
    assert monitor.active_runs() == []
    (done,) = monitor.recent_runs()
    assert done.report == {"version": 1, "rows": 256, "batches": 2}
    steps = [s["step"] for s in done.steps]
    assert steps == ["fold", "fold", "publish_finalize"]
    assert done.rows_total == 2 * 128 + 256  # folds + finalize rows
    # a second cycle opens a FRESH run (1:1 with published versions)
    trainer.feed(data[256:384])
    (second,) = monitor.active_runs()
    assert second.run_id != done.run_id
    # stop() mid-cycle closes the dangling run as aborted
    trainer.stop(timeout=0.1)
    assert monitor.active_runs() == []
    aborted = monitor.recent_runs()[0]
    assert aborted.report == {"aborted": True, "batches": 3}


def test_streaming_trainer_inert_with_fitmon_disabled(
        tmp_path, monkeypatch, rng):
    from spark_rapids_ml_tpu.serve import ModelRegistry, StreamingTrainer

    monitor = _monitor(enabled=False)
    monkeypatch.setattr(fitmon, "_monitor", monitor)
    reg = ModelRegistry()
    trainer = StreamingTrainer(
        reg, "fitmon_off", 8, 2,
        batches_per_version=1, artifact_dir=str(tmp_path))
    data = rng.normal(size=(128, 8))
    assert trainer.feed(data) == 1  # publishing still works
    assert monitor.active_runs() == []
    assert monitor.recent_runs() == []
