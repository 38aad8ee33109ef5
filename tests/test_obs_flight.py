"""Flight recorder (obs.flight): dump contents, the watchdog firing on a
stalled phase, exception dumps, and the memory watermark reader."""

import glob
import json
import os
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import obs
from spark_rapids_ml_tpu.obs import flight


@pytest.fixture
def dumps(tmp_path, monkeypatch):
    monkeypatch.setenv(flight.DUMP_DIR_ENV, str(tmp_path))
    return tmp_path


def _dump_files(dumps):
    return sorted(glob.glob(os.path.join(str(dumps), "flightdump_*.json")))


def _wait_for_dump(dumps, timeout=5.0):
    deadline_t = time.monotonic() + timeout
    while time.monotonic() < deadline_t:
        files = _dump_files(dumps)
        if files:
            return files
        time.sleep(0.05)
    raise AssertionError("no flight dump appeared")


def test_dump_contents(dumps):
    with obs.span("flight_open_span"):
        path = flight.dump("unit_test", extra={"marker": 42})
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["reason"] == "unit_test"
    assert doc["extra"]["marker"] == 42
    assert doc["pid"] == os.getpid()
    # all-thread stacks, including this one
    assert doc["thread_stacks"]
    assert any("test_dump_contents" in "".join(stack)
               for stack in doc["thread_stacks"].values())
    # the span open at dump time is visible
    assert any(s["name"] == "flight_open_span" for s in doc["open_spans"])
    # the ring tail and a metrics snapshot ride along
    assert isinstance(doc["span_ring_tail"], list)
    assert isinstance(doc["metrics"], dict)
    assert "JAX_PLATFORMS" in doc["env"]


def test_watchdog_fires_on_stalled_phase(dumps):
    """An artificially stalled phase produces a dump naming the phase."""
    with obs.deadline("stalled_phase_test", budget_seconds=0.15,
                      what="unit test"):
        _wait_for_dump(dumps)
    (path,) = _dump_files(dumps)
    doc = json.load(open(path))
    assert doc["reason"] == "budget_exceeded:stalled_phase_test"
    assert doc["extra"]["budget_info"]["what"] == "unit test"


def test_watchdog_does_not_fire_within_budget(dumps):
    with obs.deadline("fast_phase_test", budget_seconds=30.0):
        time.sleep(0.05)
    time.sleep(0.2)  # give a (wrongly) armed watchdog a chance to misfire
    assert _dump_files(dumps) == []


def test_fit_budget_env_arms_instrumented_fits(dumps, monkeypatch):
    from spark_rapids_ml_tpu.obs import fit_instrumentation

    monkeypatch.setenv(flight.FIT_BUDGET_ENV, "0.15")

    @fit_instrumentation("flight_stall_fit")
    def stalled_fit(x):
        _wait_for_dump(dumps)
        return x

    stalled_fit(np.ones((4, 2)))
    (path,) = _dump_files(dumps)
    doc = json.load(open(path))
    assert doc["reason"] == "budget_exceeded:fit:flight_stall_fit"


def test_hard_exception_dumps_fast_validation_does_not(dumps):
    # hard runtime error -> dump
    with pytest.raises(OSError):
        with obs.deadline("hard_error_test", budget_seconds=30.0):
            raise OSError("device backend gone")
    files = _dump_files(dumps)
    assert len(files) == 1
    doc = json.load(open(files[0]))
    assert doc["reason"] == "unhandled_exception:hard_error_test"
    assert "device backend gone" in doc["extra"]["error"]
    # fast validation error -> no new dump
    with pytest.raises(ValueError):
        with obs.deadline("validation_error_test", budget_seconds=30.0):
            raise ValueError("k must be set")
    assert len(_dump_files(dumps)) == 1


def test_dump_counts_in_metrics(dumps):
    reg = obs.get_registry()
    counter = reg.counter("sparkml_flight_dumps_total",
                          "flight-recorder dumps", ("reason",))
    before = counter.value(reason="metrics_probe")
    flight.dump("metrics_probe:extra_detail")
    assert counter.value(reason="metrics_probe") == before + 1


def test_memory_watermarks_cpu_fallback():
    wm = obs.memory_watermarks()
    # CPU backend exposes no PJRT stats: the host RSS watermark steps in,
    # visibly host-sourced
    assert wm["source"] in ("pjrt", "host_rss")
    assert wm["peak_bytes"] and wm["peak_bytes"] > 0
    assert wm["host_peak_rss_bytes"] > 0
    assert len(wm["per_device"]) >= 1
    import jax

    assert obs.peak_bytes_in_use(jax.devices()[0]) is None or \
        obs.peak_bytes_in_use(jax.devices()[0]) > 0


def test_record_memory_metrics_sets_gauge():
    obs.record_memory_metrics()
    reg = obs.get_registry()
    gauge = reg.gauge("sparkml_host_peak_rss_bytes",
                      "process RSS high-watermark")
    assert gauge.value() > 0


def test_active_spans_cross_thread_visibility():
    import threading

    seen = {}
    release = threading.Event()

    def worker():
        with obs.span("cross_thread_span"):
            seen["ready"] = True
            release.wait(timeout=5)

    t = threading.Thread(target=worker)
    t.start()
    try:
        for _ in range(100):
            if seen.get("ready"):
                break
            time.sleep(0.01)
        names = [s["name"] for s in obs.active_spans()]
        assert "cross_thread_span" in names
    finally:
        release.set()
        t.join()
    names = [s["name"] for s in obs.active_spans()]
    assert "cross_thread_span" not in names
