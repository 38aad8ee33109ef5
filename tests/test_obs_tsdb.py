"""obs.tsdb: ring/downsample correctness, counter math, the sampler.

Everything runs under an injectable clock — 30 minutes of samples cost
zero real seconds — plus one real-thread concurrency case (8 threads
sampling vs querying) because the store's lock discipline is exactly
what the background sampler leans on.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_ml_tpu.obs import flight
from spark_rapids_ml_tpu.obs.metrics import MetricsRegistry
from spark_rapids_ml_tpu.obs.tsdb import (
    MetricsSampler,
    TimeSeriesStore,
    counter_increase,
    default_tiers,
)
from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def store(clock):
    return TimeSeriesStore(tiers=((1.0, 10.0), (5.0, 60.0)), clock=clock)


# -- rings and downsampling --------------------------------------------------


def test_ring_bounded_and_evicts_oldest(store, clock):
    for i in range(30):
        store.record("sparkml_serve_queue_depth", {"model": "m"}, i,
                     now=1000.0 + i)
    clock.t = 1030.0
    out = store.range_query("sparkml_serve_queue_depth", window=10.0)
    pts = out[0]["points"]
    # finest tier: span 10 s at 1 s resolution -> 11 buckets max, and
    # the OLDEST samples are gone, newest kept
    assert len(pts) <= 11
    assert pts[-1] == [1029.0, 29.0]
    assert pts[0][0] >= 1019.0


def test_timestamps_monotonic_and_last_in_bucket_wins(store, clock):
    # three samples inside one 1 s bucket: the last value wins
    for value, ts in ((1.0, 1000.1), (2.0, 1000.5), (3.0, 1000.9)):
        store.record("g", {}, value, now=ts)
    store.record("g", {}, 7.0, now=1001.2)
    clock.t = 1002.0
    pts = store.range_query("g", window=10.0)[0]["points"]
    assert pts == [[1000.0, 3.0], [1001.0, 7.0]]
    assert all(a[0] < b[0] for a, b in zip(pts, pts[1:]))


def test_downsample_tier_serves_wide_windows(store, clock):
    # 40 s of 1 Hz samples: a 10 s window reads the fine tier, a 40 s
    # window falls to the 5 s tier (fine tier's span can't cover it)
    for i in range(40):
        store.record("g", {"model": "m"}, float(i), now=1000.0 + i)
    clock.t = 1040.0
    fine = store.range_query("g", window=8.0)[0]["points"]
    coarse = store.range_query("g", window=40.0)[0]["points"]
    assert all(b[0] - a[0] == 1.0 for a, b in zip(fine, fine[1:]))
    assert all(b[0] - a[0] == 5.0 for a, b in zip(coarse, coarse[1:]))
    # coarse buckets carry the LAST sample of each 5 s bucket
    assert coarse[-1][1] == 39.0
    assert coarse[-2][1] == 34.0


def test_clock_going_backwards_never_breaks_monotonicity(store, clock):
    store.record("g", {}, 1.0, now=1005.0)
    store.record("g", {}, 2.0, now=1001.0)  # stale timestamp: dropped
    clock.t = 1010.0
    pts = store.range_query("g", window=60.0)[0]["points"]
    assert pts == [[1005.0, 1.0]]


def test_label_matching_and_series_listing(store, clock):
    store.record("n", {"model": "a"}, 1.0, now=1000.0)
    store.record("n", {"model": "b"}, 2.0, now=1000.0)
    store.record("other", {}, 3.0, now=1000.0)
    clock.t = 1001.0
    assert len(store.range_query("n", window=10.0)) == 2
    only_a = store.range_query("n", {"model": "a"}, window=10.0)
    assert len(only_a) == 1 and only_a[0]["labels"] == {"model": "a"}
    assert store.series_names() == ["n", "other"]
    assert store.series_count() == 3


def test_max_series_drops_are_counted(clock):
    store = TimeSeriesStore(tiers=((1.0, 10.0),), clock=clock,
                            max_series=2)
    store.record("n", {"i": "1"}, 1.0, now=1000.0)
    store.record("n", {"i": "2"}, 1.0, now=1000.0)
    store.record("n", {"i": "3"}, 1.0, now=1000.0)  # over the cap
    assert store.series_count() == 2
    assert store.dropped_series() == 1
    # the sampler re-offers the same over-cap series every sweep: each
    # DISTINCT series counts once, not once per rejected sample
    store.record("n", {"i": "3"}, 2.0, now=1001.0)
    store.record("n", {"i": "3"}, 3.0, now=1002.0)
    assert store.dropped_series() == 1
    store.record("n", {"i": "4"}, 1.0, now=1002.0)
    assert store.dropped_series() == 2


def test_default_tiers_env_parsing(monkeypatch):
    monkeypatch.setenv(tsdb_mod.HISTORY_ENV, "2x120,30x7200")
    assert default_tiers() == ((2.0, 120.0), (30.0, 7200.0))
    monkeypatch.setenv(tsdb_mod.HISTORY_ENV, "garbage")
    assert default_tiers() == tsdb_mod.DEFAULT_TIERS
    monkeypatch.setenv(tsdb_mod.HISTORY_ENV, "5x2")  # span <= res
    assert default_tiers() == tsdb_mod.DEFAULT_TIERS


# -- counter math ------------------------------------------------------------


def test_counter_increase_handles_resets():
    # 0→5→10, reset, 2→7: increase = 5+5 + 2(post-reset) + 5 = 17
    assert counter_increase(
        [[0, 0], [1, 5], [2, 10], [3, 2], [4, 7]]) == 17.0
    assert counter_increase([[0, 3]]) == 0.0
    assert counter_increase([]) == 0.0


def test_windowed_increase_credits_births_inside_the_window(store,
                                                            clock):
    # a burst mints the child between two samples: its first sampled
    # value is already 3 — first-to-last increase alone reads 0 and a
    # windowed detector is blind to exactly the burst it watches for
    store.record("c", {"o": "err"}, 3.0, kind="counter", now=1000.0)
    store.record("c", {"o": "err"}, 3.0, kind="counter", now=1001.0)
    clock.t = 1002.0
    series = store.range_query("c", window=60.0)[0]
    assert series["born_ts"] == 1000.0
    assert tsdb_mod.counter_increase(series["points"]) == 0.0
    assert tsdb_mod.windowed_increase(series, 1002.0 - 60.0) == 3.0
    # the same series queried long after birth: the first value is now
    # just the window edge of an old counter, not new increase
    store.record("c", {"o": "err"}, 5.0, kind="counter", now=1200.0)
    clock.t = 1201.0
    series = store.range_query("c", window=5.0)[0]
    assert tsdb_mod.windowed_increase(series, 1201.0 - 5.0) == 0.0
    assert tsdb_mod.windowed_increase({"points": [], "born_ts": None},
                                      0.0) == 0.0


def test_rate_and_delta_over_reset(store, clock):
    values = [0, 10, 20, 5, 15]  # reset between 20 and 5
    for i, v in enumerate(values):
        store.record("c", {"model": "m"}, v, kind="counter",
                     now=1000.0 + i)
    clock.t = 1004.0
    assert store.delta("c", window=10.0) == 10 + 10 + 5 + 10
    assert store.rate("c", window=10.0) == pytest.approx(35.0 / 4.0)
    rp = store.rate_points("c", window=10.0)[0]["points"]
    assert [r for _ts, r in rp] == [10.0, 10.0, 5.0, 10.0]


def test_rate_zero_with_single_sample(store, clock):
    store.record("c", {}, 5.0, kind="counter", now=1000.0)
    clock.t = 1001.0
    assert store.rate("c", window=10.0) == 0.0
    assert store.delta("c", window=10.0) == 0.0


# -- concurrency -------------------------------------------------------------


def test_concurrent_sample_vs_query_8_threads():
    store = TimeSeriesStore(tiers=((0.001, 1.0), (0.01, 10.0)))
    stop = threading.Event()
    errors = []

    def writer(i):
        n = 0
        while not stop.is_set():
            try:
                store.record("c", {"w": str(i)}, n, kind="counter")
                store.record("g", {"w": str(i)}, n % 7)
                n += 1
            except Exception as exc:  # pragma: no cover
                errors.append(exc)
                return

    def reader():
        while not stop.is_set():
            try:
                for s in store.range_query("c", window=5.0):
                    pts = s["points"]
                    assert all(a[0] <= b[0]
                               for a, b in zip(pts, pts[1:]))
                store.rate("c", window=5.0)
                store.history_tail(prefixes=("c", "g"), window=5.0)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)
                return

    threads = ([threading.Thread(target=writer, args=(i,))
                for i in range(4)]
               + [threading.Thread(target=reader) for _ in range(4)])
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors
    assert store.series_count() == 8  # 4 writers x 2 names


# -- the sampler -------------------------------------------------------------


def _fixture_registry():
    reg = MetricsRegistry()
    reg.counter("sparkml_serve_requests_total", "", ("model", "outcome"))
    reg.gauge("sparkml_serve_queue_depth", "", ("model",))
    reg.summary("sparkml_serve_request_latency_seconds", "", ("model",))
    reg.histogram("sparkml_serve_h", "", ("model",))
    reg.counter("unrelated_total", "")
    return reg


def test_sampler_snapshots_selected_families(clock):
    reg = _fixture_registry()
    reg.counter("sparkml_serve_requests_total", "",
                ("model", "outcome")).inc(5, model="m", outcome="ok")
    reg.gauge("sparkml_serve_queue_depth", "",
              ("model",)).set(3, model="m")
    summary = reg.summary("sparkml_serve_request_latency_seconds", "",
                          ("model",))
    for v in (0.01, 0.02, 0.03, 0.5):
        summary.observe(v, model="m")
    reg.histogram("sparkml_serve_h", "", ("model",)).observe(
        0.2, model="m")
    reg.counter("unrelated_total", "").inc(9)
    store = TimeSeriesStore(tiers=((1.0, 300.0),), clock=clock)
    sampler = MetricsSampler(store, registry=reg, interval_seconds=1.0,
                             clock=clock)
    n = sampler.sample_once(now=1000.0)
    assert n > 0
    names = store.series_names()
    assert "sparkml_serve_requests_total" in names
    assert "sparkml_serve_queue_depth" in names
    # summaries sample one series per quantile + a _count counter
    assert "sparkml_serve_request_latency_seconds" in names
    assert "sparkml_serve_request_latency_seconds_count" in names
    q99 = store.range_query(
        "sparkml_serve_request_latency_seconds",
        {"quantile": "0.99"}, window=10.0, now=1000.0)
    assert len(q99) == 1 and q99[0]["points"]
    # histograms sample _count/_sum
    assert "sparkml_serve_h_count" in names
    assert "sparkml_serve_h_sum" in names
    # non-matching prefixes are not sampled
    assert "unrelated_total" not in names


def test_sampler_excludes_high_cardinality_ledger_families(clock):
    # SAMPLE_EXCLUDE: families whose per-(model, outcome/op/event)
    # children would each cost a ring ladder but whose time dimension
    # nobody queries — they stay on /metrics, not in the store. The
    # families the dashboard reads over time DO land.
    reg = _fixture_registry()
    reg.counter("sparkml_model_ledger_mutations_total", "",
                ("model", "op")).inc(3, model="m", op="charge_memory")
    reg.counter("sparkml_model_requests_total", "",
                ("model", "outcome")).inc(2, model="m", outcome="ok")
    reg.gauge("sparkml_model_hbm_bytes", "",
              ("model", "component")).set(512, model="m",
                                          component="weights")
    reg.counter("sparkml_model_device_seconds_total", "",
                ("model",)).inc(0.25, model="m")
    store = TimeSeriesStore(tiers=((1.0, 300.0),), clock=clock)
    sampler = MetricsSampler(store, registry=reg, interval_seconds=1.0,
                             clock=clock)
    assert sampler.sample_once(now=1000.0) > 0
    names = store.series_names()
    assert "sparkml_model_hbm_bytes" in names
    assert "sparkml_model_device_seconds_total" in names
    for excluded in ("sparkml_model_ledger_mutations_total",
                     "sparkml_model_requests_total"):
        assert excluded in tsdb_mod.SAMPLE_EXCLUDE
        assert excluded not in names


def test_sampler_counter_delta_matches_registry(clock):
    reg = _fixture_registry()
    counter = reg.counter("sparkml_serve_requests_total", "",
                          ("model", "outcome"))
    store = TimeSeriesStore(tiers=((1.0, 3600.0),), clock=clock)
    sampler = MetricsSampler(store, registry=reg, interval_seconds=1.0,
                             clock=clock)
    sampler.sample_once(now=1000.0)
    total = 0
    for i in range(30):  # 30 s of injected-clock samples
        counter.inc(i % 4, model="m", outcome="ok")
        total += i % 4
        sampler.sample_once(now=1001.0 + i)
    clock.t = 1031.0
    assert store.delta("sparkml_serve_requests_total",
                       {"model": "m"}, window=60.0) == total
    assert counter.value(model="m", outcome="ok") == total


def test_sampler_publishes_its_own_overhead(clock):
    reg = _fixture_registry()
    store = TimeSeriesStore(tiers=((1.0, 300.0),), clock=clock)
    sampler = MetricsSampler(store, registry=reg, interval_seconds=1.0,
                             clock=clock)
    sampler.sample_once(now=1000.0)
    overhead = reg.counter(
        "sparkml_obs_overhead_seconds_total", "", ("component",))
    assert overhead.value(component="sampler") > 0.0
    # the overhead counter itself is prefix-matched, so the NEXT sweep
    # gives the cost of watching its own history
    sampler.sample_once(now=1001.0)
    clock.t = 1002.0
    assert store.range_query("sparkml_obs_overhead_seconds_total",
                             window=10.0)


def test_sampler_collectors_run_and_broken_one_is_counted(clock):
    reg = _fixture_registry()
    store = TimeSeriesStore(tiers=((1.0, 300.0),), clock=clock)
    sampler = MetricsSampler(store, registry=reg, interval_seconds=1.0,
                             clock=clock)
    calls = []

    def good():
        calls.append(1)

    def broken():
        raise RuntimeError("boom")

    sampler.register_collector(good)
    sampler.register_collector(broken)
    sampler.sample_once(now=1000.0)
    assert calls == [1]
    errs = reg.counter("sparkml_obs_collector_errors_total", "",
                       ("collector",))
    assert errs.value(collector="broken") == 1.0
    sampler.unregister_collector(broken)
    sampler.sample_once(now=1001.0)
    assert errs.value(collector="broken") == 1.0


def test_sampler_background_thread_runs_and_stops():
    reg = _fixture_registry()
    reg.gauge("sparkml_serve_queue_depth", "", ("model",)).set(
        1, model="m")
    store = TimeSeriesStore(tiers=((0.01, 10.0),))
    sampler = MetricsSampler(store, registry=reg,
                             interval_seconds=0.02)
    sampler.start()
    sampler.start()  # idempotent
    time.sleep(0.2)
    sampler.stop()
    assert sampler.sweeps >= 3
    assert not sampler.running
    sweeps = sampler.sweeps
    time.sleep(0.05)
    assert sampler.sweeps == sweeps  # really stopped


_EXIT_MID_SWEEP = """
import atexit, threading, time
from spark_rapids_ml_tpu.obs.metrics import MetricsRegistry
from spark_rapids_ml_tpu.obs.tsdb import MetricsSampler, TimeSeriesStore

state = {"in_call": False}

def backend_teardown():  # registered first, so it runs last: JAX's clean_up
    print("in_call", state["in_call"], "running", sampler.running)

atexit.register(backend_teardown)
entered = threading.Event()

def collector():  # a sweep inside a device-runtime call (memory_stats)
    state["in_call"] = True
    entered.set()
    time.sleep(0.5)
    state["in_call"] = False

sampler = MetricsSampler(TimeSeriesStore(), registry=MetricsRegistry(),
                         interval_seconds=0.01)
sampler.register_collector(collector)
sampler.start()
assert entered.wait(10)
"""


def test_running_sampler_is_joined_before_later_exit_hooks():
    """Nobody stops the process-wide sampler ``start_serve_server``
    starts. A daemon sweep still inside a PJRT call when the interpreter
    tears the backend down aborts the process (seen on the v5e and, one
    run in six, on the CPU — PR 21), so a started sampler stops and joins
    itself at exit, ahead of the hooks registered before it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    proc = subprocess.run([sys.executable, "-c", _EXIT_MID_SWEEP], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["in_call", "False", "running", "False"]


_EXIT_WITH_A_LIVE_SERVER = """
import http.client, json
import numpy as np
from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.serve import (
    ModelRegistry, ServeEngine, start_serve_server)

x = np.random.default_rng(0).standard_normal((512, 32)).astype(np.float32)
registry = ModelRegistry()
registry.register("m", PCA().setK(4).fit(x))
engine = ServeEngine(registry, max_batch_rows=32, max_wait_ms=1.0)
engine.warmup("m")
server = start_serve_server(engine, port=0)
conn = http.client.HTTPConnection(
    "127.0.0.1", server.server_address[1], timeout=30)
conn.request("POST", "/predict",
             body=json.dumps({"model": "m", "rows": x[:3].tolist()}),
             headers={"Content-Type": "application/json"})
assert conn.getresponse().status == 200
print("answered", flush=True)
# exit with the server, the engine and the sampler all still running
"""


def test_process_with_a_live_serve_server_exits_clean():
    """The symptom itself: at a 1 ms sweep cadence a process that leaves
    ``start_serve_server`` running aborted at exit 12 times in 12 before
    the sampler stopped itself (``FATAL: exception not rethrown`` from
    inside the device-memory collector), 0 in 12 after (PR 21)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo,
               SPARK_RAPIDS_ML_TPU_OBS_SAMPLE_MS="1")
    proc = subprocess.run([sys.executable, "-c", _EXIT_WITH_A_LIVE_SERVER],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.stdout.split() == ["answered"], proc.stderr[-2000:]
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_stopped_sampler_leaves_no_exit_hook_behind():
    """stop() takes the hook back, so samplers that come and go (tests,
    reset_tsdb) are not kept alive until exit with their stores."""
    import gc
    import weakref

    sampler = MetricsSampler(TimeSeriesStore(), registry=MetricsRegistry(),
                             interval_seconds=0.01)
    sampler.start()
    sampler.stop()
    ref = weakref.ref(sampler)
    del sampler
    gc.collect()
    assert ref() is None


# -- history tail + flight dump integration ----------------------------------


def test_history_tail_filters_prefixes(store, clock):
    store.record("sparkml_serve_queue_depth", {"model": "m"}, 2.0,
                 now=1000.0)
    store.record("sparkml_slo_burn_rate", {"slo": "s", "window": "5m"},
                 0.5, now=1000.0)
    store.record("sparkml_http_requests_total", {}, 9.0, now=1000.0)
    clock.t = 1001.0
    tail = store.history_tail(window=300.0)
    assert "sparkml_serve_queue_depth{model=m}" in tail
    assert "sparkml_slo_burn_rate{slo=s,window=5m}" in tail
    assert not any(k.startswith("sparkml_http_") for k in tail)


def test_flight_dump_embeds_metrics_history_tail():
    tsdb_mod.reset_tsdb()
    sampler = tsdb_mod.start_sampling(interval_seconds=3600.0)
    try:
        assert sampler.running
        # Freeze the sweeps and drop what the first one captured: under
        # the full suite the process registry carries hundreds of
        # sparkml_serve_ series from other tests, and the dump tail's
        # series cap would truncate this test's series away. The
        # registered dump section reads the store via get_tsdb(), so a
        # fresh store is what the dump sees.
        tsdb_mod.stop_sampling()
        tsdb_mod.reset_tsdb()
        store = tsdb_mod.get_tsdb()
        now = time.time()
        for i in range(5):
            store.record("sparkml_serve_queue_depth",
                         {"model": "dumped"}, i, now=now - 5 + i)
        doc = flight.build_dump("test_history_tail")
        tail = doc["metrics_history"]
        assert "sparkml_serve_queue_depth{model=dumped}" in tail
        pts = tail["sparkml_serve_queue_depth{model=dumped}"]["points"]
        assert pts and pts[-1][1] == 4.0
    finally:
        tsdb_mod.stop_sampling()
        flight.unregister_dump_section("metrics_history")
        tsdb_mod.reset_tsdb()
