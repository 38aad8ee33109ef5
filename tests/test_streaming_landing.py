"""Every put's landing a span on its own chip's line (CPU).

Each device has a landing watcher for the process (``streaming._Watcher``):
a FIFO of the puts to that chip and a daemon thread that, oldest first, opens
the span ``stream:landing/<device id>``, blocks until the batch is on the chip
(``streaming.landing_of``) and closes it. The CPU lands a put at once, so the
tests stand in for the chip through that one function, the way
``test_streaming_window.py`` does through ``wait_for_landing`` — which the
watchers never call: the main thread's waits stay the put window's. What must
hold: one landing a put, on the put's chip, in the chip's put order; none for
a batch replayed from the keep; the counters; the fit's trace id on the
ring's events; and no watcher working, or holding a batch, once the fit has
said ``all_landed`` — or a moment after it has died.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from benchmarks.deploy import spark_stage
from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.utils.timing import PhaseTimer

N, BATCH, K = 24, 32, 3
EVERYTHING = 1 << 40
# how the rows are handed over → (input form, keep budget, passes that put)
FORMS = {
    "one_pass": ("iterator", 0, 1),
    "two_pass": ("callable", 0, 2),  # the CPU as it is: both passes put
    "two_pass_kept": ("callable", EVERYTHING, 1),  # the chip: pass 1 only
}
JOIN_SECONDS = 20.0


def _chunks(batches: int, seed: int = 38) -> list:
    rng = np.random.default_rng(seed)
    rows = BATCH * batches
    x = (rng.normal(size=(rows, N)) + 2.0 * (np.arange(N) % 5)).astype(
        np.float32)
    return [x[: rows // 2], x[rows // 2:]]


def _dataset(input_form: str, chunks: list):
    return (lambda: list(chunks)) if input_form == "callable" else iter(chunks)


def _budget(monkeypatch, nbytes: int) -> None:
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda device, reserve_nbytes: nbytes)


def _all_watchers() -> list:
    with streaming._WATCHERS_LOCK:
        return list(streaming._WATCHERS.values())


def _queued() -> list:
    """The watchers with a put still queued: none, once a fit has said
    ``all_landed`` (it waited behind its last put's landing on each)."""
    return [w.span for w in _all_watchers() if not w.fifo.empty()]


def _drained() -> bool:
    """Every watcher gets through what it was handed (a dying fit's puts
    land by themselves, a moment after the fit) and idles."""
    fences = [w.fence() for w in _all_watchers()]
    return all(seen.wait(JOIN_SECONDS) for seen in fences) and not _queued()


def _watcher_threads() -> list:
    return [t.name for t in threading.enumerate()
            if t.name.startswith(streaming.SPAN_LANDING)]


class _Log:
    """Puts (main thread) and landings (watcher threads) as they happened,
    arrays by identity; ``wait_for_landing`` logged apart."""

    def __init__(self, monkeypatch, seconds: float = 0.0):
        self.lock = threading.Lock()
        self.puts = []  # (chip index, device batch)
        self.landings = []  # (watcher thread's name, device batch)
        self.window_waits = []  # (thread name, device batch)
        put = streaming.IngestTrace.put

        def recording_put(ingest, batch, mask, dtype):
            c, x_dev, m_dev = put(ingest, batch, mask, dtype)
            with self.lock:
                self.puts.append((c, x_dev))
            return c, x_dev, m_dev

        def landing(x_dev):
            if seconds:
                time.sleep(seconds)
            with self.lock:
                self.landings.append(
                    (threading.current_thread().name, x_dev))

        def window_wait(x_dev):
            with self.lock:
                self.window_waits.append(
                    (threading.current_thread().name, x_dev))

        monkeypatch.setattr(streaming.IngestTrace, "put", recording_put)
        monkeypatch.setattr(streaming, "landing_of", landing)
        monkeypatch.setattr(streaming, "wait_for_landing", window_wait)

    def assert_one_landing_a_put_on_its_chip_in_order(self, devices) -> None:
        assert len(self.landings) == len(self.puts)
        for c, device in enumerate(devices):
            name = f"{streaming.SPAN_LANDING}/{device.id}"
            landed = [x for thread, x in self.landings if thread == name]
            put = [x for chip, x in self.puts if chip == c]
            assert len(landed) == len(put)
            assert all(a is b for a, b in zip(landed, put))


def _stream(dataset, chips=None, timer=None, **kwargs):
    device = None if chips is None else tuple(jax.local_devices()[:chips])
    ingest = streaming.IngestTrace(timer, device=device)
    source = BatchSource(dataset, batch_rows=BATCH)
    cov, _, count = streaming.stream_covariance(source, ingest=ingest,
                                                **kwargs)
    jax.block_until_ready(cov)
    ingest.all_landed()
    return int(count), ingest


def _landing_events(trace_id=None) -> list:
    return [e for e in obs_spans.get_recorder().events(trace_id)
            if e.name.startswith(streaming.SPAN_LANDING)]


# -- one landing a put --------------------------------------------------------


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_one_landing_a_put_on_the_puts_chip_in_put_order(monkeypatch, form,
                                                         chips):
    input_form, budget, putting_passes = FORMS[form]
    _budget(monkeypatch, budget)
    log = _Log(monkeypatch)
    batches = 4 * chips
    t0 = time.perf_counter()
    count, ingest = _stream(_dataset(input_form, _chunks(batches)),
                            chips=chips)
    wall = time.perf_counter() - t0
    assert count == BATCH * batches
    c = ingest.counters
    # a batch replayed from the keep is not put, and does not land again
    assert len(log.puts) == c["batches"] == putting_passes * batches
    log.assert_one_landing_a_put_on_its_chip_in_order(
        [chip.stats_device() for chip in ingest.chips])
    per_chip = putting_passes * batches // chips
    for chip in c["per_chip"]:
        assert chip["landings"] == per_chip
        assert 0.0 < chip["crossing_seconds"] \
            <= chip["last_landing_seconds"] <= wall
    # the fit's is the fullest chip's, and the phase is its crossing
    assert c["crossing_seconds"] == max(
        chip["crossing_seconds"] for chip in c["per_chip"])
    assert ingest.timer.as_dict()[streaming.PHASE_CROSSING] \
        == pytest.approx(c["crossing_seconds"])
    # the watchers wait through their own seam: every ``wait_for_landing``
    # is the main thread's, for the put window
    assert all(thread == threading.main_thread().name
               for thread, _ in log.window_waits)
    assert len(log.window_waits) == c["put_waits"]
    assert not _queued()
    # one thread a device for the process, however many fits
    assert len(_watcher_threads()) == len(set(_watcher_threads())) \
        <= len(jax.local_devices())


def test_a_landing_span_runs_from_the_chips_previous_landing(monkeypatch):
    """A chip's landings are serial: with every landing 20 ms long and the
    puts issued at once, each span starts where the one before ended."""
    seconds = 0.02
    monkeypatch.setattr(streaming, "PUTS_IN_FLIGHT", 8)  # no window wait
    _Log(monkeypatch, seconds=seconds)
    with obs_spans.span("landing-test") as trace_id:
        _, ingest = _stream(_dataset("iterator", _chunks(4)))
    events = sorted(_landing_events(trace_id), key=lambda e: e.ts_us)
    assert len(events) == 4
    for before, after in zip(events, events[1:]):
        assert after.ts_us >= before.ts_us + before.dur_us - 1.0
    assert all(e.dur_us >= seconds * 1e6 for e in events)
    chip, = ingest.counters["per_chip"]
    assert chip["last_landing_seconds"] >= chip["crossing_seconds"] \
        >= 4 * seconds
    # the ring's seconds are the counter's: one clock
    assert sum(e.dur_us for e in events) / 1e6 == pytest.approx(
        chip["crossing_seconds"])


@pytest.mark.parametrize("chips", [1, 4])
def test_the_rings_events_carry_the_fits_trace_id_and_the_put(chips):
    chunks = _chunks(2 * chips)
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").setNumDevices(chips).fit(iter(chunks))
    report = model.fit_report_
    events = _landing_events(report.trace_id)
    ingest = report.extra["ingest"]
    assert len(events) == ingest["batches"] == 2 * chips
    assert sorted(e.args["put"] for e in events) == list(range(2 * chips))
    for e in events:
        device_id = int(e.name.rsplit("/", 1)[1])
        assert e.args["chip"] == device_id
        assert e.args["bytes"] == BATCH * N * 4
        # batches are dealt in turn: put i goes to chip i mod chips
        assert jax.local_devices()[e.args["put"] % chips].id == device_id
        assert e.tid != threading.get_ident()  # the watcher's own line
    assert sum(chip["landings"] for chip in ingest["per_chip"]) == 2 * chips
    assert 0.0 < model.fit_timings_[streaming.PHASE_CROSSING] \
        <= report.wall_seconds
    assert report.phases[streaming.PHASE_CROSSING] \
        == model.fit_timings_[streaming.PHASE_CROSSING]
    assert not _queued()


def test_a_spark_tasks_puts_land_on_the_tasks_own_watcher(monkeypatch):
    """The Spark front: every executor task is a caller of the one loop
    with an ``IngestTrace`` of its own, so each task's puts have their
    landings, under the fit's trace id, and the fit's counters and
    ``covariance/crossing`` are the tasks' summed."""
    log = _Log(monkeypatch)
    est = spark_stage.SparkStagePCA()
    for name, value in {"k": K, "batchRows": BATCH, "executorDevice": "on",
                        "recordBatchRows": 20, "arrowColumn": "features"
                        }.items():
        est.set(name, value)
    rows = (3 * BATCH, 2 * BATCH)
    rng = np.random.default_rng(7)
    fit = est.fit(iter([(rng.normal(size=(r, N)) + 0.5).astype(np.float32)
                        for r in rows]))
    ingest = fit.fit_report_.extra["ingest"]
    assert ingest["batches"] == 5 == len(log.puts) == len(log.landings)
    assert all(a is b for (_, a), (_, b) in zip(log.landings, log.puts))
    assert ingest["per_chip"][0]["landings"] == 5
    events = _landing_events(fit.fit_report_.trace_id)
    # a task counts its puts from 0
    assert sorted(e.args["put"] for e in events) == [0, 0, 1, 1, 2]
    assert fit.fit_timings_[streaming.PHASE_CROSSING] == pytest.approx(
        ingest["crossing_seconds"])
    assert ingest["crossing_seconds"] <= fit.fit_timings_["stage/task"]
    assert not _queued()


def test_the_tasks_counters_as_one_fits():
    """Landings and the seconds a put was outstanding add up over the
    tasks; a task's last landing counts from the task's own first put, so
    the fit's is the longest task's, not a sum of offsets."""
    from spark_rapids_ml_tpu.spark.device_aggregate import sum_ingest_counters

    def task(landings, crossing, last):
        return {"crossing_seconds": crossing, "per_chip": [{
            "device": "TPU_0", "landings": landings,
            "crossing_seconds": crossing, "last_landing_seconds": last}]}

    fit = sum_ingest_counters([task(3, 0.25, 0.5), task(2, 0.5, 0.75)])
    assert fit == task(5, 0.75, 0.75)


def test_a_fit_with_no_put_has_the_phase_at_zero():
    timer = PhaseTimer()
    ingest = streaming.IngestTrace(timer)
    ingest.release()
    ingest.all_landed()
    assert timer.as_dict()[streaming.PHASE_CROSSING] == 0.0
    assert ingest.counters["crossing_seconds"] == 0.0
    for key in streaming.LANDING_COUNTERS:
        assert ingest.counters["per_chip"][0][key] == 0
    assert ingest.chips[0].watcher is None  # nothing was asked of one


# -- nothing left behind ------------------------------------------------------


def test_no_watcher_holds_a_batch_past_its_landing(monkeypatch):
    """The watcher drops a batch the moment it has landed: with the main
    thread's references gone too, the device batches of a fit that is still
    running are collected."""
    refs = []
    landed = threading.Event()

    def landing(x_dev):
        refs.append(weakref.ref(x_dev))
        landed.set()

    monkeypatch.setattr(streaming, "landing_of", landing)
    ingest = streaming.IngestTrace()
    x = np.ones((BATCH, N), np.float32)
    _, x_dev, _ = ingest.put(x, None, np.float32)
    assert landed.wait(JOIN_SECONDS)
    ingest.release()  # the window's reference
    del x_dev
    assert _drained()
    gc.collect()
    assert [r() for r in refs] == [None]
    ingest.all_landed()


class _Boom(RuntimeError):
    pass


def _raising_source():
    yield from _chunks(4)[:1]
    raise _Boom("the source died")


def _raising_step(monkeypatch, after: int) -> None:
    calls = []
    step = streaming.update_stats_auto

    def failing(stats, batch, mask=None, precision=None):
        calls.append(1)
        if len(calls) > after:
            raise _Boom("the step died")
        return step(stats, batch, mask, precision=precision)

    monkeypatch.setattr(streaming, "update_stats_auto", failing)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("dies_in", ["source", "step"])
def test_a_fit_that_dies_leaves_no_watcher_at_work(monkeypatch, dies_in,
                                                   chips):
    log = _Log(monkeypatch, seconds=0.005)
    with monkeypatch.context() as dying:
        if dies_in == "source":
            dataset = _raising_source()
        else:
            _raising_step(dying, after=2)
            dataset = iter(_chunks(4 * chips))
        with pytest.raises(_Boom):
            PCA().setK(K).set("batchRows", BATCH).set(
                "dtype", "float32").setNumDevices(chips).fit(dataset)
    # nobody said ``all_landed``: the watchers get through the fit's puts
    # as they land, into counters nobody reads, and idle
    assert _drained()
    assert len(log.landings) == len(log.puts) > 0
    # and the next fit of the process is as any other
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").setNumDevices(chips).fit(iter(_chunks(4 * chips)))
    ingest = model.fit_report_.extra["ingest"]
    assert sum(chip["landings"] for chip in ingest["per_chip"]) \
        == ingest["batches"] == 4 * chips
    assert not _queued()


def test_a_landing_that_raises_ends_its_span_and_the_watcher_goes_on(
        monkeypatch):
    seen = []

    def landing(x_dev):
        seen.append(x_dev)
        if len(seen) == 1:
            raise _Boom("the transfer failed")

    monkeypatch.setattr(streaming, "landing_of", landing)
    _, ingest = _stream(_dataset("iterator", _chunks(4)))
    assert ingest.counters["per_chip"][0]["landings"] == 4 == len(seen)
    assert not _queued()


def test_a_hand_fed_stream_goes_on_after_it_said_all_landed(monkeypatch):
    """``DistributedStreamingPCA`` goes on after ``finalize``: the chip's
    counters go on, and the phase gains what was added since. A stream
    dropped unfinished leaves nothing behind but its device's one idle
    watcher."""
    log = _Log(monkeypatch, seconds=0.005)
    ingest = streaming.IngestTrace()
    x = np.ones((BATCH, N), np.float32)
    crossed = []
    for _ in range(2):
        ingest.put(x, None, np.float32)
        ingest.release()
        ingest.all_landed()
        crossed.append(ingest.timer.as_dict()[streaming.PHASE_CROSSING])
    chip, = ingest.counters["per_chip"]
    assert chip["landings"] == 2 == len(log.landings)
    assert 0.0 < crossed[0] < crossed[1] == chip["crossing_seconds"] \
        == ingest.counters["crossing_seconds"]
    threads = _watcher_threads()
    dropped = streaming.IngestTrace()
    dropped.put(x, None, np.float32)
    del dropped
    assert _drained()
    assert _watcher_threads() == threads


def test_two_fits_at_once_keep_their_landings_apart():
    """Four fits on four threads, on the same chip and so on the same
    watcher: each put lands into its own fit's counters, under its own
    fit's trace id (a short switch interval)."""
    results, errors = {}, []

    def fit(name: str, batches: int) -> None:
        try:
            model = PCA().setK(K).set("batchRows", BATCH).set(
                "dtype", "float32").fit(iter(_chunks(batches, seed=batches)))
            results[name] = model.fit_report_
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=fit, args=(f"fit{i}", 4 + 2 * i))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(JOIN_SECONDS * 3)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    assert len({r.trace_id for r in results.values()}) == 4
    for i in range(4):
        report = results[f"fit{i}"]
        ingest = report.extra["ingest"]
        assert ingest["per_chip"][0]["landings"] == ingest["batches"] \
            == 4 + 2 * i
        events = _landing_events(report.trace_id)
        assert sorted(e.args["put"] for e in events) == list(range(4 + 2 * i))
        assert sum(e.dur_us for e in events) / 1e6 == pytest.approx(
            ingest["crossing_seconds"])
    assert not _queued()
