"""Health probe + tracing behavior (optional-by-construction, SURVEY.md §3.4)."""

import numpy as np

from spark_rapids_ml_tpu.utils.health import check_devices
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange
from spark_rapids_ml_tpu.utils.timing import PhaseTimer


def test_health_probe_cpu():
    h = check_devices()
    assert h.healthy, h.error
    assert h.platform == "cpu"
    assert h.device_count == 8
    assert len(h.devices) == 8


def test_trace_range_noop_safe():
    # No profiler session active, native lib may or may not be present:
    # ranges must work regardless (unlike the reference, whose NvtxRange
    # hard-requires the .so even on CPU paths).
    with TraceRange("outer", TraceColor.RED) as tr:
        with TraceRange("inner", TraceColor.GREEN):
            x = np.ones(10).sum()
    assert x == 10.0
    assert tr.elapsed >= 0.0


def test_trace_range_survives_exceptions():
    try:
        with TraceRange("failing"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    # balanced: a following range still works
    with TraceRange("after"):
        pass


def test_phase_timer_accumulates():
    t = PhaseTimer()
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    d = t.as_dict()
    assert set(d) == {"a", "b"}
    assert d["a"] >= 0.0


def test_trace_colors_match_reference_palette():
    # NvtxColor.java:20-29 ARGB values
    assert TraceColor.GREEN.value == 0xFF76B900
    assert TraceColor.RED.value == 0xFFFF0000
    assert len(TraceColor) == 9


def test_phase_timer_nested_and_total():
    t = PhaseTimer()
    with t.phase("outer"):
        with t.phase("inner"):  # re-entrant: must not deadlock or corrupt
            pass
    d = t.as_dict()
    assert set(d) == {"outer", "inner"}
    assert d["outer"] >= d["inner"]
    assert t.total() == sum(d.values())
    t.add("outer", 1.0)
    assert t.as_dict()["outer"] >= 1.0


def test_phase_timer_concurrent_threads():
    import threading

    t = PhaseTimer()

    def worker(name):
        for _ in range(200):
            with t.phase(name):
                pass

    threads = [
        threading.Thread(target=worker, args=(f"p{i % 2}",))
        for i in range(4)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert set(t.as_dict()) == {"p0", "p1"}


def test_check_devices_subprocess_timeout_verdict(monkeypatch):
    """Degraded path: a wedged backend init must come back as a structured
    unhealthy verdict naming the deadline, never a hang or a raise."""
    import subprocess

    from spark_rapids_ml_tpu.utils.health import check_devices_subprocess

    def fake_run(*args, **kwargs):
        raise subprocess.TimeoutExpired(cmd="probe", timeout=kwargs.get(
            "timeout", 0.0))

    monkeypatch.setattr(subprocess, "run", fake_run)
    verdict = check_devices_subprocess(timeout_seconds=0.25)
    assert verdict.healthy is False
    assert verdict.device_count == 0
    assert "exceeded 0.25s" in verdict.error


def test_check_devices_subprocess_crash_verdict(monkeypatch):
    """Degraded path: a crashing probe child yields a structured verdict
    carrying the child's stderr tail."""
    import subprocess

    from spark_rapids_ml_tpu.utils.health import check_devices_subprocess

    class FakeProc:
        returncode = 3
        stdout = ""
        stderr = "boom: device backend fell over"

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: FakeProc())
    verdict = check_devices_subprocess(timeout_seconds=5)
    assert verdict.healthy is False
    assert "rc=3" in verdict.error
    assert "device backend fell over" in verdict.error
