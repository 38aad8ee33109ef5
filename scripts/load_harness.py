#!/usr/bin/env python
"""Closed-loop multi-tenant load harness: prove overload survival.

``bench_serve`` is open-loop and single-tenant — it measures the happy
path. This harness measures the regime the ROADMAP's "millions of
users" pillar actually lives in: **sustained offered load beyond
capacity**, with tenants that do not cooperate. It stands up the REAL
stack (fitted PCA model → registry → engine with quotas + weighted-fair
scheduling + adaptive shedding → stdlib HTTP server) and drives it from
closed-loop client threads over the wire:

1. **calibrate** — one well-behaved tenant, closed loop, measures
   single-tenant capacity (rows/sec at the configured concurrency);
2. **overload soak** (``SPARKML_LOAD_SOAK_SECONDS``, default 60) — two
   tenants at once:

   * ``compliant`` — interactive priority, paced (Poisson think time)
     at ~25% of capacity, inside its 30% quota: the tenant the
     fairness contract protects;
   * ``greedy`` — batch priority, zero think time from
     ``SPARKML_LOAD_GREEDY_THREADS`` closed-loop threads, quota 45% of
     capacity, request size AUTO-SCALED from calibration so its flood
     pushes TOTAL offered load past 2× capacity — everything beyond
     its quota is the over-quota excess the controller sheds. (The
     quota split is work-conserving: in-quota greedy + compliant
     traffic together carry near-capacity throughput while the excess
     absorbs every rejection. The 10×-over-quota starvation case lives
     in tests/test_serve_fairness.py with an injected clock.)

The robustness acceptance judged on the emitted record:

* compliant availability ≥ ``SPARKML_LOAD_MIN_AVAILABILITY`` (0.99) and
  compliant p99 within its SLO (``SPARKML_LOAD_P99_MS``, default the
  serve latency SLO threshold) — the greedy flood cannot starve the
  in-SLO tenant;
* total served throughput ≥ ``SPARKML_LOAD_THROUGHPUT_FRACTION`` (0.9)
  × calibrated capacity — shedding sheds *excess*, not *capacity*;
* every circuit breaker CLOSED at the end — overload must never read
  as backend failure (the PR 6 invariant, extended);
* the shedding lands on the greedy tenant (its availability and shed
  counts are in the record; the compliant tenant's sheds must be 0).

Emits ONE ``bench_common.emit_record`` line the perf sentinel judges
(metric ``load_harness_compliant_availability``, explicitly
higher-is-better) — committed history lives in
``records/load_harness_r*.json``. Exit 0 = all gates pass.

CPU-only harness: the device-scaling, ramp, accounting, fitmon, density
and fleet phases spawn children on forced host devices
(``--xla_force_host_platform_device_count``, CPU backend only) and the
fleet phase runs two serving peers at once, so every child is pinned to
``JAX_PLATFORMS=cpu`` — a chip belongs to one process. Nothing printed
here is a device number.

Knobs (env): SPARKML_LOAD_SOAK_SECONDS (60),
SPARKML_LOAD_CALIBRATE_SECONDS (8), SPARKML_LOAD_FEATURES (32),
SPARKML_LOAD_K (8), SPARKML_LOAD_GREEDY_THREADS (12),
SPARKML_LOAD_COMPLIANT_THREADS (4), SPARKML_LOAD_MIN_AVAILABILITY
(0.99), SPARKML_LOAD_THROUGHPUT_FRACTION (0.9), SPARKML_LOAD_P99_MS
(the SLO threshold), plus every SPARK_RAPIDS_ML_TPU_SERVE_* engine knob.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

# The overload soak WILL open SLO-burn incidents (that is the point) —
# but an incident-triggered jax profile capture mid-soak would measure
# the profiler, not the scheduler (start_trace wedges on this
# container's CPU backend under live traffic — the PR 7 lesson). Set
# BEFORE the package import, like the chaos drill.
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S", "0")

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench_common  # noqa: E402 (scripts/ on path when run directly)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _post_predict(base: str, body: bytes, tenant: str, priority: str,
                  timeout: float = 30.0):
    """One HTTP predict; (status, retry_after_s, shed). Never raises.

    Tenant/priority ride the HEADERS (as well as the body) so the
    server's pre-parse fast-shed path can identify the request class
    without touching the payload."""
    req = urllib.request.Request(
        f"{base}/predict", data=body,
        headers={"Content-Type": "application/json",
                 "X-Tenant": tenant, "X-Priority": priority},
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        resp.read()
        return resp.status, None, False
    except urllib.error.HTTPError as exc:
        retry_after = exc.headers.get("Retry-After")
        try:
            payload = json.loads(exc.read())
        except ValueError:
            payload = {}
        return (exc.code,
                float(retry_after) if retry_after else None,
                bool(payload.get("shed")))
    except Exception:  # noqa: BLE001 - a hang/reset IS the measurement
        return 0, None, False


class TenantLoad:
    """One tenant's closed-loop client fleet.

    Each thread loops: think (exponential, ``pace_rps`` per thread; 0 =
    no think time — pure closed loop), pick a request size, POST, record
    (status, latency, rows, shed). ``stop_at`` ends the phase."""

    def __init__(self, base: str, model: str, x: np.ndarray, *,
                 tenant: str, priority: str, threads: int,
                 pace_rps_per_thread: float, rows_lo: int, rows_hi: int,
                 reject_pause_s: float = 0.01,
                 deadline_ms: float = 0.0, seed: int = 0):
        self.base = base
        self.model = model
        self.x = x
        self.tenant = tenant
        self.priority = priority
        self.threads = threads
        self.pace = pace_rps_per_thread
        self.rows_lo, self.rows_hi = rows_lo, rows_hi
        self.reject_pause_s = reject_pause_s
        self.deadline_ms = deadline_ms
        self.seed = seed
        self.lock = threading.Lock()
        self.results = []  # (status, latency_s, rows, shed)

    def _client(self, idx: int, stop_at: float) -> None:
        rng = np.random.default_rng(self.seed * 1000 + idx)
        while time.monotonic() < stop_at:
            if self.pace > 0:
                think = float(rng.exponential(1.0 / self.pace))
                if time.monotonic() + think >= stop_at:
                    return
                time.sleep(think)
            n = int(rng.integers(self.rows_lo, self.rows_hi + 1))
            start = int(rng.integers(0, self.x.shape[0] - n))
            payload = {
                "model": self.model,
                "rows": self.x[start:start + n].tolist(),
                "tenant": self.tenant,
                "priority": self.priority,
            }
            if self.deadline_ms > 0:
                payload["deadline_ms"] = self.deadline_ms
            body = json.dumps(payload).encode()
            t0 = time.perf_counter()
            status, _retry_after, shed = _post_predict(
                self.base, body, self.tenant, self.priority)
            latency = time.perf_counter() - t0
            with self.lock:
                self.results.append((status, latency, n, shed))
            if status != 200 and self.reject_pause_s > 0:
                # a rejected closed-loop client spinning at MHz would
                # measure the client, not the server — tiny pause only
                time.sleep(self.reject_pause_s)

    def run(self, seconds: float) -> None:
        stop_at = time.monotonic() + seconds
        workers = [
            threading.Thread(target=self._client, args=(i, stop_at),
                             daemon=True)
            for i in range(self.threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(seconds + 60.0)

    def stats(self, wall: float) -> dict:
        with self.lock:
            results = list(self.results)
        attempts = len(results)
        ok = [(lat, n) for s, lat, n, _ in results if s == 200]
        lat_ok = sorted(lat for lat, _n in ok)
        served_rows = sum(n for _lat, n in ok)

        def pct(q: float) -> float:
            if not lat_ok:
                return 0.0
            return lat_ok[min(int(q * len(lat_ok)), len(lat_ok) - 1)]

        return {
            "tenant": self.tenant,
            "priority": self.priority,
            "threads": self.threads,
            "attempts": attempts,
            "ok": len(ok),
            "availability": len(ok) / attempts if attempts else 0.0,
            "shed": sum(1 for s, _l, _n, shed in results
                        if shed and s != 200),
            "rejected_429": sum(1 for s, *_ in results if s == 429),
            "status_5xx": sum(1 for s, *_ in results
                              if 500 <= s <= 599),
            "timeouts_504": sum(1 for s, *_ in results if s == 504),
            "hung": sum(1 for s, *_ in results if s == 0),
            "offered_rps": attempts / wall if wall > 0 else 0.0,
            "offered_rows_per_sec": (sum(n for _s, _l, n, _ in results)
                                     / wall if wall > 0 else 0.0),
            "served_rows_per_sec": (served_rows / wall
                                    if wall > 0 else 0.0),
            "p50": pct(0.50),
            "p99": pct(0.99),
        }


def _get_json(base: str, path: str) -> dict:
    try:
        resp = urllib.request.urlopen(f"{base}{path}", timeout=10.0)
        return json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        try:
            return json.loads(exc.read())
        except ValueError:
            return {}
    except Exception:  # noqa: BLE001 - a dead ops endpoint IS a finding
        return {}


DEVICE_CHILD_PREFIX = "DEVICE_CAPACITY_RESULT "


def device_capacity_child() -> int:
    """One device count's capacity calibration (run in its own process
    — device count is fixed at jax init): a closed-loop single-tenant
    load over the REAL HTTP server with a modeled per-batch device
    service time (``SPARKML_LOAD_DEVICE_MS``, default 40 — a GIL-
    released latency fault at every replica dispatch, same CPU-CI
    honesty note as ``bench_serve``'s multidevice scenario: a 1-core
    container cannot show FLOPS parallelism, so the phase judges the
    TIER's capacity scaling; set 0 on real hardware)."""
    import jax

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        fault_plane,
        start_serve_server,
    )

    seconds = _env_float("SPARKML_LOAD_DEVICE_SECONDS", 8.0)
    device_ms = _env_float("SPARKML_LOAD_DEVICE_MS", 40.0)
    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    k = _env_int("SPARKML_LOAD_K", 8)
    rng = np.random.default_rng(23)
    x = rng.normal(size=(2048, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("load_md_pca", model)
    engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=2.0,
                         max_queue_depth=256)
    engine.warmup("load_md_pca")
    if device_ms > 0:
        fault_plane().inject("load_md_pca", "latency", count=None,
                             seconds=device_ms / 1000.0)
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    # full-bucket requests: one request = one modeled device dispatch,
    # so measured capacity is the tier's dispatch concurrency (see the
    # bench_serve multidevice rationale)
    load = TenantLoad(base, "load_md_pca", x, tenant="calibrate",
                      priority="interactive", threads=12,
                      pace_rps_per_thread=0.0, rows_lo=256, rows_hi=256,
                      seed=5)
    t0 = time.monotonic()
    load.run(seconds)
    wall = time.monotonic() - t0
    stats = load.stats(wall)
    server.shutdown()
    engine.shutdown()
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(1.0)
    result = {
        "devices": len(jax.devices()),
        "modeled_device_ms": device_ms,
        "seconds": wall,
        "capacity_rows_per_sec": stats["served_rows_per_sec"],
        "availability": stats["availability"],
        "p50_ms": stats["p50"] * 1000.0,
        "p99_ms": stats["p99"] * 1000.0,
        "hung": stats["hung"],
    }
    sys.stdout.write(DEVICE_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def run_device_scaling_phase() -> dict:
    """Capacity at 1 vs 2 devices, each in its own subprocess: the
    device-scaling gate — 2-device capacity must be >= 1.6x the
    1-device calibration with compliant p99 under the single-device
    bar."""
    import subprocess

    results = {}
    for n in (1, 2):
        env = dict(os.environ)
        env["SPARKML_LOAD_PHASE"] = "device_capacity_child"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = bench_common.force_device_count_flags(n)
        env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
        bench_common.log(f"load_harness device scaling: child at "
                         f"{n} device(s)")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=420,
        )
        result = bench_common.prefixed_result(proc.stdout,
                                              DEVICE_CHILD_PREFIX)
        if result is None:
            return {"error": f"device child at {n} produced no result "
                             f"(rc={proc.returncode}): "
                             f"{proc.stderr[-1500:]}"}
        results[n] = result
    base_cap = results[1]["capacity_rows_per_sec"]
    ratio = (results[2]["capacity_rows_per_sec"] / base_cap
             if base_cap else 0.0)
    # the single-device bar: the same derivation the soak uses — the
    # SLO latency threshold or 2x the single-device tail, whichever is
    # looser (adding a device must not make the protected tail worse)
    bar_ms = max(
        _env_float("SPARK_RAPIDS_ML_TPU_SLO_LATENCY_THRESHOLD_MS",
                   250.0),
        2.0 * results[1]["p99_ms"])
    return {
        "one_device": results[1],
        "two_devices": results[2],
        "capacity_ratio": ratio,
        "p99_bar_ms": bar_ms,
        "p99_under_bar": results[2]["p99_ms"] <= bar_ms,
    }


RAMP_CHILD_PREFIX = "RAMP_CHILD_RESULT "


def ramp_child() -> int:
    """The autoscale ramp phase (own process — forced 4 host devices):
    offered load ramps 1× → 3× → 1× of single-replica capacity while an
    ``AutoscaleController`` moves the replica count against the live
    queue-wait/shed/burn/occupancy signals. Modeled per-batch device
    service time (``SPARKML_LOAD_RAMP_DEVICE_MS``, default 40 — the
    same CPU-CI honesty device as the other multi-device phases) makes
    capacity replica-bound, so the controller's decisions are the
    thing under test, not this container's FLOPS."""
    import json

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        AutoscaleController,
        ModelRegistry,
        ServeEngine,
        fault_plane,
        start_serve_server,
    )

    seg_s = _env_float("SPARKML_LOAD_RAMP_SEGMENT_S", 12.0)
    down_s = _env_float("SPARKML_LOAD_RAMP_DOWN_S", 18.0)
    device_ms = _env_float("SPARKML_LOAD_RAMP_DEVICE_MS", 40.0)
    unit_rps = _env_float("SPARKML_LOAD_RAMP_UNIT_RPS", 12.0)
    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    k = _env_int("SPARKML_LOAD_K", 8)
    rng = np.random.default_rng(29)
    x = rng.normal(size=(2048, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("ramp_pca", model)
    engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=2.0,
                         max_queue_depth=512)
    # warm the FULL ladder at full scale first (on a real deploy the
    # persistent executable cache makes this a disk replay), then start
    # scaled down to min — scale-up must be cheap because warm
    engine.warmup("ramp_pca")
    engine.scale_replicas(1)
    if device_ms > 0:
        fault_plane().inject("ramp_pca", "latency", count=None,
                             seconds=device_ms / 1000.0)
    controller = AutoscaleController(
        engine, min_replicas=1, max_replicas=4, interval_s=0.25,
        up_queue_wait_s=0.06, up_hold_s=0.5, down_hold_s=3.0,
        cooldown_s=1.5, down_queue_wait_s=0.02, down_occupancy=0.55,
        up_occupancy=0.9,
    )
    controller.start()
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    # replica-count trajectory watcher (0.25 s cadence)
    trajectory = []
    stop_watch = threading.Event()

    def _watch() -> None:
        t_start = time.monotonic()
        while not stop_watch.is_set():
            trajectory.append((time.monotonic() - t_start,
                               engine.replica_scale()))
            time.sleep(0.25)

    watcher = threading.Thread(target=_watch, daemon=True)
    watcher.start()

    segments = []
    threads = 8
    for name, mult, seconds in (("ramp_1x_a", 1.0, seg_s),
                                ("ramp_3x", 3.0, seg_s),
                                ("ramp_1x_b", 1.0, down_s)):
        rate = unit_rps * mult
        load = TenantLoad(base, "ramp_pca", x, tenant="ramp",
                          priority="interactive", threads=threads,
                          pace_rps_per_thread=rate / threads,
                          rows_lo=256, rows_hi=256, seed=11)
        t0 = time.monotonic()
        load.run(seconds)
        wall = time.monotonic() - t0
        stats = load.stats(wall)
        # steady-state tail: drop the adaptation window after each
        # transition (the controller needs hold+cooldown to converge;
        # the phase judges the CONVERGED posture, spikes are the
        # signal that drives it)
        adapt_s = _env_float("SPARKML_LOAD_RAMP_ADAPT_S", 5.0)
        with load.lock:
            results = list(load.results)
        # results are appended in completion order; approximate the
        # adaptation cut by request count at the offered rate — but
        # never cut past what actually completed: a throughput
        # collapse must not empty the window and read as a 0.0 p99
        # (the gate would pass vacuously on the exact regression it
        # exists to catch). Fewer results than the nominal skip means
        # the "steady state" never arrived — judge the WHOLE segment.
        skip = min(int(rate * adapt_s), max(len(results) // 2, 0))
        steady = sorted(lat for s, lat, _n, _shed in results[skip:]
                        if s == 200)
        stats["steady_p99"] = (
            steady[min(int(0.99 * len(steady)), len(steady) - 1)]
            if steady else stats["p99"] or float("inf"))
        stats["segment"] = name
        stats["offered_mult"] = mult
        stats["replicas_at_end"] = engine.replica_scale()
        segments.append(stats)
    # let the down-scale hysteresis finish before the final reading
    settle_s = _env_float("SPARKML_LOAD_RAMP_SETTLE_S", 8.0)
    time.sleep(settle_s)
    stop_watch.set()
    watcher.join(2.0)
    controller.stop()
    breakers = engine.breaker_snapshot()
    history = controller.decision_history()
    snapshot = controller.snapshot()
    server.shutdown()
    engine.shutdown()
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(1.0)
    replica_counts = [r for _t, r in trajectory]
    actions = [h for h in history
               if h["decision"] in ("scale_up", "scale_down")]
    action_gaps = [round(b["at"] - a["at"], 3)
                   for a, b in zip(actions, actions[1:])]
    result = {
        "devices": 4,
        "modeled_device_ms": device_ms,
        "unit_rps": unit_rps,
        "segments": segments,
        "replicas_max": max(replica_counts, default=1),
        "replicas_end": engine.replica_scale(),
        "replica_trajectory": replica_counts,
        "scale_actions": [
            {"decision": h["decision"], "from": h["from"],
             "to": h["to"]} for h in actions],
        "action_gaps_s": action_gaps,
        "cooldown_s": controller.cooldown_s,
        "breakers_closed": all(b["state"] == "closed"
                               for b in breakers.values()),
        "autoscale_snapshot": {
            "min": snapshot["min"], "max": snapshot["max"],
            "signals": snapshot["signals"],
        },
    }
    sys.stdout.write(RAMP_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def run_ramp_phase() -> int:
    """Parent leg of the autoscale ramp phase: spawn the 4-device child,
    judge the gates, emit the sentinel record. Gates:

    * replica count RISES on the up-ramp (max ≥ 2) and RETIRES back to
      the floor on the down-ramp (end == 1);
    * compliant availability ≥ ``SPARKML_LOAD_MIN_AVAILABILITY`` (0.99)
      in every segment, steady-state p99 under the bar throughout;
    * no two scale actions closer than the hysteresis cooldown (the
      anti-flap contract);
    * every circuit breaker CLOSED (elasticity must never read as
      backend failure)."""
    import subprocess

    env = dict(os.environ)
    env["SPARKML_LOAD_PHASE"] = "ramp_child"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = bench_common.force_device_count_flags(4)
    env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
    bench_common.log("load_harness ramp: child at 4 device(s), "
                     "1x -> 3x -> 1x offered")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    result = bench_common.prefixed_result(proc.stdout, RAMP_CHILD_PREFIX)
    if result is None:
        bench_common.log(
            f"load_harness ramp FAIL: child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
        return 1
    min_availability = _env_float("SPARKML_LOAD_MIN_AVAILABILITY", 0.99)
    p99_bar_ms = _env_float(
        "SPARKML_LOAD_RAMP_P99_MS",
        max(_env_float("SPARK_RAPIDS_ML_TPU_SLO_LATENCY_THRESHOLD_MS",
                       250.0),
            8.0 * result["modeled_device_ms"]))
    availability = min(
        (s["availability"] for s in result["segments"]), default=0.0)
    worst_steady_p99_ms = max(
        (s["steady_p99"] * 1000.0 for s in result["segments"]),
        default=0.0)
    record = {
        "bench": "load_harness_ramp",
        "metric": "load_harness_ramp_availability",
        "value": availability,
        "unit": ("worst per-segment availability through a 1x->3x->1x "
                 "offered-load ramp under the autoscale controller"),
        "higher_is_better": True,
        "platform": "cpu",
        "device_kind": "cpu",
        **{k: v for k, v in result.items()
           if k != "replica_trajectory"},
        "worst_steady_p99_ms": worst_steady_p99_ms,
        "p99_bar_ms": p99_bar_ms,
    }
    bench_common.emit_record(record, include_metrics=False)
    failures = []
    if result["replicas_max"] < 2:
        failures.append(
            f"replica count never rose above "
            f"{result['replicas_max']} on the 3x up-ramp")
    if result["replicas_end"] != 1:
        failures.append(
            f"replica count ended at {result['replicas_end']}, not "
            "retired back to the 1-replica floor")
    if availability < min_availability:
        failures.append(
            f"availability {availability:.4f} < {min_availability}")
    if worst_steady_p99_ms > p99_bar_ms:
        failures.append(
            f"steady-state p99 {worst_steady_p99_ms:.0f} ms > "
            f"{p99_bar_ms:.0f} ms bar")
    if not result["breakers_closed"]:
        failures.append("a circuit breaker opened during the ramp")
    bad_gaps = [g for g in result["action_gaps_s"]
                if g < result["cooldown_s"] - 0.05]
    if bad_gaps:
        failures.append(
            f"scale actions {bad_gaps} s apart — faster than the "
            f"{result['cooldown_s']} s hysteresis cooldown (flap)")
    hung = sum(s["hung"] for s in result["segments"])
    if hung:
        failures.append(f"{hung} request(s) hung")
    if failures:
        bench_common.log("load_harness ramp FAIL: "
                         + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness ramp PASS: replicas 1 -> "
        f"{result['replicas_max']} -> {result['replicas_end']}, "
        f"availability {availability:.4f}, steady p99 "
        f"{worst_steady_p99_ms:.0f} ms (bar {p99_bar_ms:.0f}), "
        f"actions {result['scale_actions']}")
    return 0


ACCOUNTING_CHILD_PREFIX = "ACCOUNTING_CHILD_RESULT "


def accounting_child() -> int:
    """The cost-attribution phase (own process — forced 2 host devices):
    three PCA models at 2 replicas each behind the REAL HTTP server,
    driven with a Zipf-weighted mix (hot takes most of the traffic, mid
    a trickle, cold goes quiet after a brief opening burst). What the
    parent judges from this child's output:

    * the ledger's summed per-model device-seconds RECONCILE against
      the independent devmon counter (both meters ride the same batch-
      completion seam, so drift beyond the documented tolerance means
      an attribution bug, not noise);
    * the ``/debug/costs`` cold-model report ranks the idle model
      colder than the hot one — resident bytes with no traffic is
      exactly what tiering wants surfaced;
    * scale-down releases accounted residency: after the soak the hot
      model drops to 1 replica, the reap moves the retired replica's
      weights bytes into the ``reserve`` component (the program is
      RETAINED for zero-cold-start revival, not freed)."""
    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        start_serve_server,
    )

    soak_s = _env_float("SPARKML_LOAD_ACCT_SECONDS", 10.0)
    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    k = _env_int("SPARKML_LOAD_K", 8)
    rng = np.random.default_rng(31)
    x = rng.normal(size=(2048, n_features))
    registry = ModelRegistry()
    models = ("acct_hot_pca", "acct_mid_pca", "acct_cold_pca")
    for name in models:
        registry.register(name, PCA().setK(k).fit(x))
    engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=2.0,
                         max_queue_depth=256)
    for name in models:
        engine.warmup(name)
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    # opening burst: every model takes a little traffic, so the cold
    # model has real rows on the meter — "cold" must mean went-idle
    # (age + ewma), not never-seen
    for name in models:
        burst = TenantLoad(base, name, x, tenant="acct",
                           priority="interactive", threads=2,
                           pace_rps_per_thread=0.0, rows_lo=16,
                           rows_hi=64, seed=7)
        burst.run(1.0)
    # Zipf-weighted soak: hot closed-loop, mid paced at a trickle, cold
    # silent — the 1/rank^s shape collapsed onto three tiers
    hot = TenantLoad(base, "acct_hot_pca", x, tenant="acct",
                     priority="interactive", threads=6,
                     pace_rps_per_thread=0.0, rows_lo=16, rows_hi=96,
                     seed=8)
    mid = TenantLoad(base, "acct_mid_pca", x, tenant="acct",
                     priority="interactive", threads=2,
                     pace_rps_per_thread=4.0, rows_lo=8, rows_hi=32,
                     seed=9)
    t0 = time.monotonic()
    threads = [
        threading.Thread(target=hot.run, args=(soak_s,), daemon=True),
        threading.Thread(target=mid.run, args=(soak_s,), daemon=True),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(soak_s + 60.0)
    wall = time.monotonic() - t0
    # let in-flight batches complete so both meters stop moving, then
    # read the rollup over the wire — the endpoint under test
    time.sleep(1.0)
    costs = _get_json(base, "/debug/costs")

    # scale-down leg: hot model to 1 replica, reap, re-read residency
    weights_before = {
        m: costs.get("models", {}).get(m, {}).get(
            "hbm_bytes", {}).get("weights", 0)
        for m in models
    }
    # scale_replicas reaps drained retirees itself; the loop only
    # covers replicas whose queues were still draining at that instant
    scale_report = engine.scale_replicas(1)
    retired = sum(d.get("retired", 0)
                  for d in scale_report.get("resized", {}).values())
    reap_deadline = time.monotonic() + 20.0
    while time.monotonic() < reap_deadline:
        engine.reap_retired()
        if engine.replica_scale() == 1:
            break
        time.sleep(0.25)
    costs_after = _get_json(base, "/debug/costs")
    hot_after = costs_after.get("models", {}).get(
        "acct_hot_pca", {}).get("hbm_bytes", {})

    server.shutdown()
    engine.shutdown()
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(1.0)

    hot_stats = hot.stats(wall)
    mid_stats = mid.stats(wall)
    # live replicas only — synthetic rows like "(sharded)" / "(aot)"
    # must not satisfy the >= 2-replica gate
    replica_counts = {
        m: sum(1 for key in
               costs.get("models", {}).get(m, {}).get("replicas", {})
               if not key.startswith("("))
        for m in models
    }
    result = {
        "devices": 2,
        "soak_seconds": wall,
        "hot_served_rows_per_sec": hot_stats["served_rows_per_sec"],
        "mid_served_rows_per_sec": mid_stats["served_rows_per_sec"],
        "hot_availability": hot_stats["availability"],
        "replica_counts": replica_counts,
        "reconcile": costs.get("reconcile", {}),
        "cold_report": costs.get("cold_report", []),
        "models": {
            m: {key: doc.get(key) for key in
                ("hbm_total_bytes", "device_seconds", "rows",
                 "ewma_rps", "last_hit_age_seconds")}
            for m, doc in costs.get("models", {}).items()
        },
        "weights_before": weights_before,
        "hot_weights_after": hot_after.get("weights", -1),
        "hot_reserve_after": hot_after.get("reserve", -1),
        "retired": retired,
    }
    sys.stdout.write(ACCOUNTING_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def run_accounting_phase() -> int:
    """Parent leg of the cost-attribution phase: spawn the 2-device
    child, judge the gates, emit the sentinel record. Gates:

    * ledger-vs-devmon reconciliation verdict ``ok`` with worst drift
      within the documented tolerance
      (``SPARK_RAPIDS_ML_TPU_OBS_RECONCILE_TOL``, default 5%), at
      least one model over the attribution floor;
    * the cold-model report ranks the idle model colder than the hot
      one under the Zipf mix;
    * every model ran >= 2 replicas, and the scale-down reap moved the
      hot model's retired weights bytes into ``reserve`` (released
      from the live-weights component, retained for revival)."""
    import subprocess

    env = dict(os.environ)
    env["SPARKML_LOAD_PHASE"] = "accounting_child"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = bench_common.force_device_count_flags(2)
    env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
    bench_common.log("load_harness accounting: child at 2 device(s), "
                     "Zipf hot/mid/cold mix across 3 models")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=420,
    )
    result = bench_common.prefixed_result(proc.stdout,
                                          ACCOUNTING_CHILD_PREFIX)
    if result is None:
        bench_common.log(
            f"load_harness accounting FAIL: child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
        return 1
    reconcile = result["reconcile"]
    worst_drift = float(reconcile.get("worst_drift_ratio", 1.0))
    tolerance = float(reconcile.get("tolerance", 0.0))
    cold_rank = {doc["model"]: i
                 for i, doc in enumerate(result["cold_report"])}
    record = {
        "bench": "load_harness_accounting",
        "metric": "load_harness_accounting_worst_drift",
        "value": worst_drift,
        "unit": ("worst per-model relative drift between ledger and "
                 "devmon device-seconds at the batch-completion seam"),
        "higher_is_better": False,
        "platform": "cpu",
        "device_kind": "cpu",
        **{key: result[key] for key in
           ("devices", "soak_seconds", "replica_counts", "reconcile",
            "cold_report", "models", "weights_before",
            "hot_weights_after", "hot_reserve_after", "retired",
            "hot_served_rows_per_sec", "hot_availability")},
    }
    bench_common.emit_record(record, include_metrics=False)
    failures = []
    if reconcile.get("verdict") != "ok":
        failures.append(
            f"reconcile verdict {reconcile.get('verdict')!r} "
            f"(worst drift {worst_drift:.4f} vs tolerance "
            f"{tolerance:.4f})")
    if int(reconcile.get("models_checked", 0)) < 1:
        failures.append("no model crossed the reconcile attribution "
                        "floor — the soak metered nothing")
    if cold_rank.get("acct_cold_pca", 99) > cold_rank.get(
            "acct_hot_pca", -1):
        failures.append(
            f"cold report ranked hot before idle: {cold_rank}")
    thin = {m: n for m, n in result["replica_counts"].items() if n < 2}
    if thin:
        failures.append(f"models below 2 replicas during the soak: "
                        f"{thin}")
    hot_before = int(result["weights_before"].get("acct_hot_pca", 0))
    if not (0 <= result["hot_weights_after"] < hot_before):
        failures.append(
            f"scale-down did not release accounted weights bytes: "
            f"{hot_before} -> {result['hot_weights_after']}")
    if result["hot_reserve_after"] <= 0:
        failures.append(
            "reaped replica's bytes did not land in the reserve "
            "component — the retained program would be invisible")
    if failures:
        bench_common.log("load_harness accounting FAIL: "
                         + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness accounting PASS: worst drift "
        f"{worst_drift:.4f} (tolerance {tolerance:.4f}, "
        f"{reconcile.get('models_checked')} model(s) checked), cold "
        f"report ranks {result['cold_report'][0]['model']} coldest, "
        f"hot weights {hot_before} -> {result['hot_weights_after']} "
        f"bytes with {result['hot_reserve_after']} in reserve after "
        f"scale-down")
    return 0


FITMON_CHILD_PREFIX = "FITMON_CHILD_RESULT "


def fitmon_child() -> int:
    """The fit-observability phase (own process — forced 2 host devices,
    fast sampler, fast watchdog, 1-sweep incident hysteresis). Four
    drills, all judged by the parent:

    * **visibility** — PCA and KMeans fits under the live stack (every
      ``@fit_instrumentation`` driver opens a FitRun), then
      ``GET /debug/fit`` over the wire must show per-step device time,
      rows/sec, and MFU for both algos. CPU has no real peak table, so
      the parent injects a synthetic one via
      ``SPARK_RAPIDS_ML_TPU_FITMON_PEAK_FLOPS`` — absent MFU on a
      configured-peaks backend is a broken attribution path, not an
      unknown device kind;
    * **reconcile** — fitmon's summed ``sparkml_fit_device_seconds_
      total`` against devmon's ``fit:*`` batch-seconds (the one
      measured duration feeds both meters, so drift is an attribution
      bug, not noise);
    * **straggler** — an injected per-host delay in a run's host-step
      table must trip the straggler flag for exactly that host;
    * **watchdog** — flipping the watchdog's expected platform to
      "tpu" (resolved: cpu) must open exactly ONE auto-resolving
      ``fit_backend_degraded`` incident; clearing the expectation must
      resolve it."""
    import jax

    from spark_rapids_ml_tpu.obs import fitmon, get_registry
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        start_serve_server,
    )

    n_features = _env_int("SPARKML_LOAD_FEATURES", 32)
    k = _env_int("SPARKML_LOAD_K", 8)
    n_fits = _env_int("SPARKML_LOAD_FITMON_FITS", 3)

    registry = ModelRegistry()
    engine = ServeEngine(registry, max_batch_rows=128, max_wait_ms=2.0,
                         max_queue_depth=64)
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    def metric_sum(name: str, label: str = None,
                   prefix: str = None) -> float:
        snap = get_registry().snapshot().get(name, {"samples": []})
        total = 0.0
        for s in snap["samples"]:
            if prefix is not None and not str(
                    s["labels"].get(label, "")).startswith(prefix):
                continue
            total += s["value"]
        return total

    # -- visibility: monitored DISTRIBUTED fits under the live stack -------
    # (the parallel drivers are the instrumented surface — the forced
    # 2-device mesh is exactly what a real pod slice shard looks like)
    from spark_rapids_ml_tpu.parallel import (
        distributed_kmeans_fit,
        distributed_pca_fit,
    )
    from spark_rapids_ml_tpu.parallel.mesh import data_mesh

    mesh = data_mesh()
    rng = np.random.default_rng(11)
    x = rng.normal(size=(4096, n_features))
    for seed in range(n_fits):
        distributed_pca_fit(x, k, mesh)
        distributed_kmeans_fit(x, k, mesh, max_iter=10, seed=seed)
    fit_doc = _get_json(base, "/debug/fit")
    runs = fit_doc.get("recent", []) + fit_doc.get("active", [])

    def algo_evidence(algo: str) -> dict:
        mine = [r for r in runs if r.get("algo") == algo]
        return {
            "runs": len(mine),
            "steps": sum(r.get("steps", 0) for r in mine),
            "device_seconds": sum(
                r.get("device_seconds") or 0.0 for r in mine),
            "rows_per_sec_present": any(
                r.get("rows_per_sec") for r in mine),
            "mfu_present": any(
                r.get("mfu_mean") is not None for r in mine),
        }

    evidence = {
        "distributed_pca": algo_evidence("distributed_pca"),
        "distributed_kmeans": algo_evidence("distributed_kmeans"),
    }

    # -- reconcile: fitmon device-seconds vs the devmon meter --------------
    fitmon_s = metric_sum("sparkml_fit_device_seconds_total")
    devmon_s = metric_sum("sparkml_serve_device_batch_seconds_total",
                          label="model", prefix="fit:")
    drift = (abs(fitmon_s - devmon_s) / fitmon_s) if fitmon_s > 0 else 1.0

    # -- straggler: injected per-host delay --------------------------------
    monitor = fitmon.get_fit_monitor()
    run = monitor.start_run("straggler_drill")
    with run.step("drill", rows=256):
        pass
    run.note_host_step("host0", 0.10)
    run.note_host_step("host1", 0.11)
    run.note_host_step("host2", 0.45)  # the injected delay
    skew = run.skew()
    monitor.finish_run(run)

    # -- watchdog: platform-mismatch drill over the REAL pipeline ----------
    # (watchdog check → gauge → sampler sweep → ThresholdDetector →
    # incident engine), all on the live sampler thread at its fast
    # cadence. The expectation flip is the injected fault.
    wd = monitor.watchdog

    def fit_backend_incidents(doc: dict, state: str) -> list:
        return [i for i in doc.get(state, [])
                if i.get("detector") == fitmon.INCIDENT_NAME]

    def wait_for(predicate, timeout_s: float = 30.0) -> dict:
        deadline = time.monotonic() + timeout_s
        doc = {}
        while time.monotonic() < deadline:
            doc = _get_json(base, "/debug/incidents")
            if predicate(doc):
                return doc
            time.sleep(0.2)
        return doc

    wd.expected_platform = None
    wd.check()  # healthy baseline lands backend_ok=1 in the store
    time.sleep(1.0)
    wd.expected_platform = "tpu"  # resolved platform is cpu: degraded
    opened_doc = wait_for(
        lambda d: len(fit_backend_incidents(d, "open")) >= 1)
    open_incidents = fit_backend_incidents(opened_doc, "open")
    mismatch_verdict = wd.last_verdict() or {}
    wd.expected_platform = None  # fault cleared: must auto-resolve
    resolved_doc = wait_for(
        lambda d: not fit_backend_incidents(d, "open")
        and fit_backend_incidents(d, "recent"))
    resolved = fit_backend_incidents(resolved_doc, "recent")

    server.shutdown()
    engine.shutdown()
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(1.0)

    result = {
        "devices": jax.device_count(),
        "fits_per_algo": n_fits,
        "algos": evidence,
        "fit_doc_peaks": fit_doc.get("peaks", {}),
        "fitmon_device_seconds": fitmon_s,
        "devmon_fit_batch_seconds": devmon_s,
        "device_seconds_drift": drift,
        "skew": skew,
        "watchdog_mismatch_verdict": {
            key: mismatch_verdict.get(key)
            for key in ("ok", "reason", "platform", "expected_platform")
        },
        "incidents_opened": len(open_incidents),
        "incident_detectors": sorted(
            {i.get("detector") for i in open_incidents}),
        "incidents_resolved": len(resolved),
        "incident_states": sorted(
            {i.get("state") for i in resolved}),
    }
    sys.stdout.write(FITMON_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def run_fitmon_phase() -> int:
    """Parent leg of the fit-observability phase: spawn the 2-device
    child with fast observability cadences, judge the gates, emit the
    sentinel record. Gates:

    * both fitted algos show steps with device time, rows/sec, AND MFU
      in ``/debug/fit`` (synthetic peak table injected — MFU absent
      would mean the TrackedJit→fitmon attribution path is severed);
    * fitmon's device-seconds reconcile with devmon's ``fit:*`` meter
      within ``SPARKML_LOAD_FITMON_DRIFT`` (default 5%);
    * the injected per-host delay flags exactly that host a straggler;
    * the platform-mismatch drill opens exactly one
      ``fit_backend_degraded`` incident and it auto-resolves once the
      expectation is cleared."""
    import subprocess

    drift_bar = _env_float("SPARKML_LOAD_FITMON_DRIFT", 0.05)
    env = dict(os.environ)
    env["SPARKML_LOAD_PHASE"] = "fitmon_child"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = bench_common.force_device_count_flags(2)
    env["SPARK_RAPIDS_ML_TPU_OBS_SAMPLE_MS"] = "100"
    env["SPARK_RAPIDS_ML_TPU_FITMON_WATCHDOG_S"] = "0.2"
    env["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_OPEN_AFTER"] = "1"
    env["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_RESOLVE_AFTER"] = "2"
    env["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_COOLDOWN_S"] = "0"
    env["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S"] = "0"
    # CPU has no peak table; a synthetic one makes MFU a hard assertion
    env["SPARK_RAPIDS_ML_TPU_FITMON_PEAK_FLOPS"] = "1e12"
    env["SPARK_RAPIDS_ML_TPU_FITMON_PEAK_BW"] = "1e11"
    env.pop("SPARK_RAPIDS_ML_TPU_FITMON_EXPECT_PLATFORM", None)
    bench_common.log("load_harness fitmon: child at 2 device(s), "
                     "PCA+KMeans fits + straggler + watchdog drills")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=420,
    )
    result = bench_common.prefixed_result(proc.stdout,
                                          FITMON_CHILD_PREFIX)
    if result is None:
        bench_common.log(
            f"load_harness fitmon FAIL: child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
        return 1
    drift = float(result["device_seconds_drift"])
    record = {
        "bench": "load_harness_fitmon",
        "metric": "load_harness_fitmon_device_drift",
        "value": drift,
        "unit": ("relative drift between fitmon step device-seconds "
                 "and the devmon fit:* batch meter"),
        "higher_is_better": False,
        "platform": "cpu",
        "device_kind": "cpu",
        "drift_bar": drift_bar,
        **{key: result[key] for key in
           ("devices", "fits_per_algo", "algos", "fitmon_device_seconds",
            "devmon_fit_batch_seconds", "skew",
            "watchdog_mismatch_verdict", "incidents_opened",
            "incident_detectors", "incidents_resolved",
            "incident_states")},
    }
    bench_common.emit_record(record, include_metrics=False)
    failures = []
    for algo, doc in result["algos"].items():
        if doc["runs"] < 1 or doc["steps"] < 1:
            failures.append(f"{algo}: no monitored runs/steps in "
                            f"/debug/fit ({doc})")
        if not doc["rows_per_sec_present"]:
            failures.append(f"{algo}: no per-step rows/sec")
        if doc["device_seconds"] <= 0:
            failures.append(f"{algo}: no per-step device time")
        if not doc["mfu_present"]:
            failures.append(f"{algo}: MFU absent despite injected peaks "
                            "— TrackedJit cost attribution severed")
    if drift > drift_bar:
        failures.append(
            f"fitmon/devmon device-seconds drift {drift:.4f} exceeds "
            f"{drift_bar:.4f} ({result['fitmon_device_seconds']:.4f}s "
            f"vs {result['devmon_fit_batch_seconds']:.4f}s)")
    if result["skew"].get("stragglers") != ["host2"]:
        failures.append(
            f"injected host2 delay not flagged: {result['skew']}")
    if result["incidents_opened"] != 1 or result[
            "incident_detectors"] != ["fit_backend_degraded"]:
        failures.append(
            f"platform-mismatch drill opened "
            f"{result['incidents_opened']} incident(s) "
            f"({result['incident_detectors']}), wanted exactly one "
            f"fit_backend_degraded")
    if result["incidents_resolved"] < 1 or result[
            "incident_states"] != ["resolved"]:
        failures.append(
            f"fit_backend_degraded did not auto-resolve after the "
            f"expectation was cleared: {result['incident_states']}")
    if failures:
        bench_common.log("load_harness fitmon FAIL: "
                         + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness fitmon PASS: {result['fits_per_algo']} fit(s) "
        f"per algo visible with MFU, device-seconds drift "
        f"{drift:.4f} (bar {drift_bar:.4f}), straggler host2 flagged, "
        f"one fit_backend_degraded incident opened and auto-resolved")
    return 0


DENSITY_CHILD_PREFIX = "DENSITY_CHILD_RESULT "


class _ZipfLoad:
    """A closed-loop client fleet whose every request samples its MODEL
    from a Zipf(s) distribution over the registry — the thousand-model
    serving mix: one hot head, a long cold tail."""

    def __init__(self, base: str, names, x: np.ndarray, *,
                 threads: int, zipf_s: float, rows_lo: int,
                 rows_hi: int, seed: int = 0):
        self.base = base
        self.names = list(names)
        self.x = x
        self.threads = threads
        self.rows_lo, self.rows_hi = rows_lo, rows_hi
        self.seed = seed
        weights = np.array(
            [1.0 / (i + 1) ** zipf_s for i in range(len(self.names))])
        self.probs = weights / weights.sum()
        self.lock = threading.Lock()
        self.results = []  # (model_idx, status, latency_s, rows)

    def _client(self, idx: int, stop_at: float) -> None:
        rng = np.random.default_rng(self.seed * 1000 + idx)
        while time.monotonic() < stop_at:
            m = int(rng.choice(len(self.names), p=self.probs))
            n = int(rng.integers(self.rows_lo, self.rows_hi + 1))
            start = int(rng.integers(0, self.x.shape[0] - n))
            body = json.dumps({
                "model": self.names[m],
                "rows": self.x[start:start + n].tolist(),
                "tenant": "density",
                "priority": "interactive",
            }).encode()
            t0 = time.perf_counter()
            status, _retry, _shed = _post_predict(
                self.base, body, "density", "interactive")
            with self.lock:
                self.results.append(
                    (m, status, time.perf_counter() - t0, n))
            if status != 200:
                time.sleep(0.01)

    def run(self, seconds: float) -> None:
        stop_at = time.monotonic() + seconds
        workers = [
            threading.Thread(target=self._client, args=(i, stop_at),
                             daemon=True)
            for i in range(self.threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(seconds + 120.0)

    def model_stats(self, idx: int) -> dict:
        with self.lock:
            mine = [(s, lat) for m, s, lat, _n in self.results
                    if m == idx]
        lat_ok = sorted(lat for s, lat in mine if s == 200)

        def pct(q: float) -> float:
            if not lat_ok:
                return 0.0
            return lat_ok[min(int(q * len(lat_ok)), len(lat_ok) - 1)]

        return {
            "attempts": len(mine),
            "ok": len(lat_ok),
            "availability": (len(lat_ok) / len(mine)) if mine else 0.0,
            "p50_ms": pct(0.50) * 1000.0,
            "p99_ms": pct(0.99) * 1000.0,
        }

    def distinct_models_hit(self) -> int:
        with self.lock:
            return len({m for m, *_ in self.results})


def density_child() -> int:
    """One arm of the model-density phase (own process — forced 2 host
    devices). Registers ``SPARKML_LOAD_DENSITY_MODELS`` names of one
    fitted PCA behind the real HTTP server, drives a Zipf mix over ALL
    of them, and — when ``SPARKML_LOAD_DENSITY_TIERING=1`` — runs the
    ``TieringController`` against a ``budget_models``-model HBM budget
    while it soaks. The control arm (tiering off) is the same stack
    with nothing ever moved off the device: its residency only grows.
    Both arms count fresh XLA compiles during the soak — reactivation
    must be a disk replay through the executable cache, never a
    recompile storm."""
    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.obs import xprof
    from spark_rapids_ml_tpu.obs.aotcache import (
        configure_executable_cache,
    )
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        TieringController,
        start_serve_server,
    )

    tiering_on = os.environ.get("SPARKML_LOAD_DENSITY_TIERING") == "1"
    n_models = _env_int("SPARKML_LOAD_DENSITY_MODELS", 200)
    budget_models = _env_int("SPARKML_LOAD_DENSITY_BUDGET_MODELS", 10)
    soak_s = _env_float("SPARKML_LOAD_DENSITY_SECONDS", 10.0)
    zipf_s = _env_float("SPARKML_LOAD_DENSITY_ZIPF_S", 1.1)
    threads = _env_int("SPARKML_LOAD_DENSITY_THREADS", 4)
    cache_dir = os.environ.get("SPARKML_LOAD_DENSITY_CACHE")
    if cache_dir:
        configure_executable_cache(cache_dir)

    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    rng = np.random.default_rng(43)
    x = rng.normal(size=(1024, n_features))
    # ONE fitted model under many names: executables are weight-
    # independent and keyed on (label, signature), so the whole roster
    # shares one compiled ladder — warming name 0 warms the fleet
    model = PCA().setK(4).fit(x)
    registry = ModelRegistry()
    names = [f"density_{i:03d}" for i in range(n_models)]
    for name in names:
        registry.register(name, model)
    engine = ServeEngine(registry, max_batch_rows=64, max_wait_ms=1.0,
                         max_queue_depth=256, buckets=(64,))
    engine.placer.set_target(1)
    engine.warmup(names[0])
    # probe one TAIL model so the budget is sized from what a lazily
    # built replica actually charges (weights only — the warmed head
    # additionally carries the roster's shared executable bytes)
    engine.predict(names[1], x[:16])
    warm_base = sum(
        engine._ledger.memory_bytes(model=names[0]).values())
    per_model = sum(
        engine._ledger.memory_bytes(model=names[1]).values())
    budget = warm_base + budget_models * per_model

    ctrl = None
    if tiering_on:
        # the hot head is pinned: its warmed base (weights + attributed
        # executable bytes) stays resident, so the byte budget confines
        # the TAIL to ~budget_models lazily built residents
        ctrl = TieringController(
            engine, hbm_budget_bytes=budget, flap_floor_s=1.0,
            interval_s=0.25, per_model_autoscale=False, enabled=True,
            pins=(names[0],))
        engine.attach_tiering(ctrl)
        ctrl.start()
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    load = _ZipfLoad(base, names, x, threads=threads, zipf_s=zipf_s,
                     rows_lo=16, rows_hi=48, seed=5)
    xprof.reset_compile_log()
    t0 = time.monotonic()
    load.run(soak_s)
    wall = time.monotonic() - t0
    time.sleep(0.5)
    soak_compiles = sum(
        s["compiles"] for s in xprof.compile_stats().values())

    tiering_doc = _get_json(base, "/debug/tiering")
    if ctrl is not None:
        ctrl.stop()
        # settle: clients are gone, so tick until the budget holds —
        # models reactivated moments ago sit inside the flap floor and
        # need one more tick after it expires
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            ctrl.evaluate_once()
            if sum(engine._ledger.memory_bytes().values()) <= budget:
                break
            time.sleep(0.3)
    resident = engine._ledger.memory_bytes()
    resident_models = sum(1 for b in resident.values() if b > 0)
    resident_bytes = sum(resident.values())

    def tiering_count(event: str) -> float:
        from spark_rapids_ml_tpu.obs import get_registry
        snap = get_registry().snapshot().get(
            "sparkml_serve_tiering_total", {"samples": []})
        return sum(s["value"] for s in snap["samples"]
                   if s["labels"].get("event") == event)

    first_hits = [h["seconds"]
                  for h in (ctrl.lifecycle_history() if ctrl else [])
                  if h["event"] == "reactivate"]
    server.shutdown()
    engine.shutdown()
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(0.5)

    result = {
        "tiering": tiering_on,
        "devices": 2,
        "models": n_models,
        "budget_models": budget_models,
        "per_model_bytes": per_model,
        "warm_base_bytes": warm_base,
        "hbm_budget_bytes": budget,
        "soak_seconds": wall,
        "soak_compiles": soak_compiles,
        "distinct_models_hit": load.distinct_models_hit(),
        "resident_models_end": resident_models,
        "resident_bytes_end": resident_bytes,
        "hot": load.model_stats(0),
        "cold_hits": tiering_count("cold_hit"),
        "reactivates": tiering_count("reactivate"),
        "deactivates": tiering_count("deactivate"),
        "max_first_hit_s": max(first_hits, default=0.0),
        "tiering_state_counts": tiering_doc.get("state_counts", {}),
    }
    sys.stdout.write(DENSITY_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def run_density_phase() -> int:
    """Parent leg of the model-density phase (ISSUE 19): spawn the
    2-device child twice — control (no tiering) and tiering under a
    ~``budget_models``-model HBM budget — over the SAME Zipf mix, judge
    the gates, emit the sentinel record. Gates:

    * the control arm's residency BLOWS THROUGH the budget (the
      problem is real on this mix: no eviction → every model ever hit
      stays resident);
    * the tiering arm ends byte-exact within the HBM budget, with the
      resident-model count at or under ``budget_models``;
    * cold first hits happened, every one completed its reactivation
      (``reactivate`` == ``cold_hit``), and the worst first-hit is
      bounded (``SPARKML_LOAD_DENSITY_FIRST_HIT_S``, default 2 s);
    * ZERO fresh XLA compiles during the tiering soak — every
      reactivation replayed through the executable cache;
    * the hot model's p99 under tiering stays within
      ``SPARKML_LOAD_DENSITY_P99_RATIO`` (default 2.5×) of the
      no-tiering control, with availability >= 0.99 in both arms —
      evicting the cold tail must not tax the hot head."""
    import subprocess
    import tempfile

    ratio_bar = _env_float("SPARKML_LOAD_DENSITY_P99_RATIO", 2.5)
    first_hit_bar = _env_float("SPARKML_LOAD_DENSITY_FIRST_HIT_S", 2.0)
    min_availability = _env_float("SPARKML_LOAD_MIN_AVAILABILITY", 0.99)
    arms = {}
    with tempfile.TemporaryDirectory(prefix="density_aot_") as tmp:
        for arm, flag in (("control", "0"), ("tiering", "1")):
            env = dict(os.environ)
            env["SPARKML_LOAD_PHASE"] = "density_child"
            env["SPARKML_LOAD_DENSITY_TIERING"] = flag
            env["SPARKML_LOAD_DENSITY_CACHE"] = os.path.join(tmp, arm)
            # 200 registered models must each keep their own ledger
            # label — the default 64-model fold would collapse the cold
            # tail into "(overflow)" and blind the eviction ranking
            env["SPARK_RAPIDS_ML_TPU_OBS_MODEL_MAX"] = "256"
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = bench_common.force_device_count_flags(2)
            env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
            bench_common.log(
                f"load_harness density: {arm} child at 2 device(s)")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=420,
            )
            result = bench_common.prefixed_result(
                proc.stdout, DENSITY_CHILD_PREFIX)
            if result is None:
                bench_common.log(
                    f"load_harness density FAIL: {arm} child produced "
                    f"no result (rc={proc.returncode}): "
                    f"{proc.stderr[-2000:]}")
                return 1
            arms[arm] = result
    control, tiering = arms["control"], arms["tiering"]
    control_p99 = float(control["hot"]["p99_ms"])
    tiering_p99 = float(tiering["hot"]["p99_ms"])
    p99_ratio = (tiering_p99 / control_p99) if control_p99 > 0 else 99.0
    record = {
        "bench": "load_harness_density",
        "metric": "load_harness_density_hot_p99_ratio",
        "value": p99_ratio,
        "unit": ("hot-model p99 under tiering vs the no-tiering "
                 "control on the same Zipf many-model mix"),
        "higher_is_better": False,
        "platform": "cpu",
        "device_kind": "cpu",
        "p99_ratio_bar": ratio_bar,
        "first_hit_bar_s": first_hit_bar,
        "control": control,
        "tiering": tiering,
    }
    bench_common.emit_record(record, include_metrics=False)
    failures = []
    if control["resident_bytes_end"] <= control["hbm_budget_bytes"]:
        failures.append(
            f"control residency {control['resident_bytes_end']} never "
            f"exceeded the budget {control['hbm_budget_bytes']} — the "
            "mix proves nothing")
    if tiering["resident_bytes_end"] > tiering["hbm_budget_bytes"]:
        failures.append(
            f"tiering residency {tiering['resident_bytes_end']} over "
            f"the {tiering['hbm_budget_bytes']}-byte budget")
    if tiering["resident_models_end"] > tiering["budget_models"] + 1:
        failures.append(
            f"{tiering['resident_models_end']} models resident, "
            f"budget {tiering['budget_models']} (+1 warmed head)")
    if tiering["cold_hits"] < 1:
        failures.append("no cold first hits — tiering never cycled")
    if tiering["reactivates"] != tiering["cold_hits"]:
        failures.append(
            f"{tiering['cold_hits']} cold hits but "
            f"{tiering['reactivates']} completed reactivations")
    if tiering["soak_compiles"] != 0:
        failures.append(
            f"{tiering['soak_compiles']} fresh XLA compile(s) during "
            "the tiering soak — reactivation is recompiling")
    if tiering["max_first_hit_s"] > first_hit_bar:
        failures.append(
            f"worst cold first-hit {tiering['max_first_hit_s']:.3f}s "
            f"> {first_hit_bar}s bar")
    if p99_ratio > ratio_bar:
        failures.append(
            f"hot p99 ratio {p99_ratio:.2f} (tiering "
            f"{tiering_p99:.0f}ms vs control {control_p99:.0f}ms) > "
            f"{ratio_bar}")
    for arm, doc in arms.items():
        if doc["hot"]["availability"] < min_availability:
            failures.append(
                f"{arm} hot availability "
                f"{doc['hot']['availability']:.4f} < "
                f"{min_availability}")
    if failures:
        bench_common.log("load_harness density FAIL: "
                         + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness density PASS: {tiering['models']} models, "
        f"{tiering['resident_models_end']} resident (budget "
        f"{tiering['budget_models']}), {int(tiering['cold_hits'])} "
        f"cold hits all reactivated with 0 fresh compiles (worst "
        f"first-hit {tiering['max_first_hit_s'] * 1000:.0f} ms), hot "
        f"p99 ratio {p99_ratio:.2f} (bar {ratio_bar})")
    return 0


FLEET_CHILD_PREFIX = "FLEET_CHILD_READY "


def fleet_child() -> int:
    """One fleet peer: a self-driving serving process on a fixed port.

    The child stands up the REAL stack (fitted PCA → registry → engine →
    HTTP server with the live sampler, so ``/debug/fleet/export`` has a
    populated store to walk) and then generates its own modest predict
    traffic forever — the parent aggregator polls it over the wire and
    SIGKILLs it mid-drill, so this function never returns normally. The
    parent pins ``SPARK_RAPIDS_ML_TPU_FLEET_HOST`` so a respawned peer
    keeps its host identity and the ``fleet_host_down`` incident
    auto-resolves instead of leaking a ghost host."""
    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        start_serve_server,
    )

    port = _env_int("SPARKML_LOAD_FLEET_PORT", 0)
    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    k = _env_int("SPARKML_LOAD_K", 4)

    rng = np.random.default_rng(5)
    x = rng.normal(size=(1024, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("fleet_pca", model)
    engine = ServeEngine(registry, max_batch_rows=128, max_wait_ms=2.0,
                         max_queue_depth=256)
    server = start_serve_server(engine, port=port)
    sys.stdout.write(FLEET_CHILD_PREFIX + json.dumps(
        {"port": server.server_address[1]}) + "\n")
    sys.stdout.flush()
    while True:  # until SIGKILL — the parent owns this lifetime
        n = int(rng.integers(8, 64))
        start = int(rng.integers(0, x.shape[0] - n))
        try:
            engine.predict("fleet_pca", x[start:start + n])
        except Exception:  # noqa: BLE001 - shed/overload is fine here
            pass
        time.sleep(0.02)


def run_fleet_phase() -> int:
    """The fleet-federation phase: 2 serving subprocesses through ONE
    in-process aggregator. The parent IS the fleet brain — it runs the
    sampler + incident engine + forecaster + ``FleetAggregator`` that a
    real deployment would run on its coordinator host. Gates:

    * both peers polled ok and the MERGED store carries the same series
      under both ``host=`` labels (federation actually federates);
    * SIGKILLing peer B opens exactly ONE ``fleet_host_down`` incident
      (for hostB only — hostA must stay clean) through the standard
      sampler→detector→incident pipeline, and respawning the peer on
      the same host identity + port auto-resolves it;
    * the Holt forecaster's backtest relative error on the fleet
      request-rate signal is under ``SPARKML_LOAD_FLEET_FORECAST_ERR``
      (default 0.5) after the soak — the predictive plane's evidence
      that its projections track reality."""
    import socket
    import subprocess

    forecast_err_bar = _env_float("SPARKML_LOAD_FLEET_FORECAST_ERR", 0.5)
    soak_s = _env_float("SPARKML_LOAD_FLEET_SOAK_SECONDS", 12.0)

    # fast cadences BEFORE the obs singletons are constructed (children
    # inherit these via the spawn env, so both sides sweep at 100 ms
    # and incidents open after 1 sweep / resolve after 2)
    os.environ["SPARK_RAPIDS_ML_TPU_OBS_SAMPLE_MS"] = "100"
    os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_OPEN_AFTER"] = "1"
    os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_RESOLVE_AFTER"] = "2"
    os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_COOLDOWN_S"] = "0"
    os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S"] = "0"

    from spark_rapids_ml_tpu.obs import (
        federation,
        forecast,
        incidents as incidents_mod,
        tsdb as tsdb_mod,
    )

    def free_port() -> int:
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        return port

    ports = {"hostA": free_port(), "hostB": free_port()}
    bases = {h: f"http://127.0.0.1:{p}" for h, p in ports.items()}
    procs: dict = {}

    def spawn(host: str) -> None:
        env = dict(os.environ)
        env["SPARKML_LOAD_PHASE"] = "fleet_child"
        env["JAX_PLATFORMS"] = "cpu"
        env["SPARKML_LOAD_FLEET_PORT"] = str(ports[host])
        env["SPARK_RAPIDS_ML_TPU_FLEET_HOST"] = host
        procs[host] = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def wait_ready(host: str, timeout_s: float = 90.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if _get_json(bases[host], "/healthz"):  # {} while booting
                return True
            time.sleep(0.2)
        return False

    def wait_for(predicate, timeout_s: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.2)
        return False

    def fleet_incidents(state: str) -> list:
        digest = inc_engine.digest()
        return [i for i in digest.get(state, [])
                if i.get("detector") == federation.INCIDENT_NAME]

    bench_common.log("load_harness fleet: spawning 2 serving peers "
                     f"(hostA:{ports['hostA']}, hostB:{ports['hostB']})")
    for host in sorted(ports):
        spawn(host)
    agg = None
    failures = []
    try:
        for host in sorted(ports):
            if not wait_ready(host):
                bench_common.log(
                    f"load_harness fleet FAIL: {host} never became "
                    f"ready on {bases[host]}")
                return 1

        sampler = tsdb_mod.start_sampling()
        inc_engine = incidents_mod.get_incident_engine()
        inc_engine.install(sampler)
        forecaster = forecast.get_forecaster()
        forecaster.install(sampler)
        agg = federation.FleetAggregator(
            [(host, bases[host]) for host in sorted(ports)],
            poll_interval_s=0.25, stale_after_s=1.0,
            fetch_timeout_s=1.0, forecaster=forecaster)
        federation.set_aggregator(agg)
        agg.start()

        # -- soak: merged series must carry BOTH host labels ---------------
        def merged_hosts() -> set:
            found = set()
            for row in agg.store().range_query(
                    "sparkml_serve_requests_total", window=120.0):
                host = row["labels"].get("host")
                if host:
                    found.add(host)
            return found

        time.sleep(soak_s)
        both_merged = wait_for(
            lambda: merged_hosts() >= set(ports), timeout_s=30.0)
        hosts_seen = sorted(merged_hosts())
        rollup = agg.rollup()
        if not both_merged:
            failures.append(
                f"merged store carries host labels {hosts_seen}, "
                f"wanted both of {sorted(ports)}")
        if rollup["hosts_up"] != len(ports):
            failures.append(
                f"{rollup['hosts_up']}/{len(ports)} hosts up after "
                f"soak: {rollup['hosts']}")

        # -- kill drill: SIGKILL hostB → exactly one fleet_host_down -------
        procs["hostB"].kill()
        procs["hostB"].wait()
        opened = wait_for(lambda: len(fleet_incidents("open")) >= 1)
        open_incs = fleet_incidents("open")
        open_hosts = sorted({(i.get("labels") or {}).get("host")
                             for i in open_incs})
        if not opened or len(open_incs) != 1 or open_hosts != ["hostB"]:
            failures.append(
                f"kill drill wanted exactly one open "
                f"{federation.INCIDENT_NAME} for hostB, got "
                f"{len(open_incs)} for hosts {open_hosts}")

        # -- respawn on the SAME identity + port → must auto-resolve -------
        spawn("hostB")
        if not wait_ready("hostB"):
            failures.append("respawned hostB never became ready")
        resolved = wait_for(
            lambda: not fleet_incidents("open")
            and any(i.get("state") == "resolved"
                    for i in fleet_incidents("recent")))
        total_fleet_incidents = (
            len(fleet_incidents("open")) + len(fleet_incidents("recent")))
        if not resolved:
            failures.append(
                f"{federation.INCIDENT_NAME} did not auto-resolve after "
                f"respawn: open={fleet_incidents('open')} "
                f"recent={fleet_incidents('recent')}")
        if total_fleet_incidents != 1:
            failures.append(
                f"kill drill produced {total_fleet_incidents} "
                f"{federation.INCIDENT_NAME} incident(s), wanted "
                f"exactly one (flapping or a ghost host)")

        # -- forecaster backtest over the merged fleet rate ----------------
        fc = forecaster.snapshot()
        rps = fc["signals"].get("rps", {})
        backtest = rps.get("backtest", {})
        rel_err = backtest.get("rel_err_mean")
        if rps.get("updates", 0) < 10 or rel_err is None:
            failures.append(
                f"forecaster starved: {rps.get('updates', 0)} rps "
                f"updates, rel_err={rel_err}")
        elif rel_err > forecast_err_bar:
            failures.append(
                f"forecast backtest rel err {rel_err:.4f} exceeds "
                f"bar {forecast_err_bar:.4f}")
        rollup = agg.rollup()
    finally:
        if agg is not None:
            agg.stop()
            federation.set_aggregator(None)
        for proc in procs.values():
            try:
                proc.kill()
            except Exception:  # noqa: BLE001 - already dead is fine
                pass

    record = {
        "bench": "load_harness_fleet",
        "metric": "load_harness_fleet_forecast_rel_err",
        "value": rel_err if rel_err is not None else 1.0,
        "unit": ("Holt backtest |err| / |value| on the merged fleet "
                 "request-rate signal over the soak"),
        "higher_is_better": False,
        "platform": "cpu",
        "device_kind": "cpu",
        "peers": len(ports),
        "soak_seconds": soak_s,
        "forecast_err_bar": forecast_err_bar,
        "merged_host_labels": hosts_seen,
        "hosts_up_after_soak": rollup["hosts_up"],
        "merged_points": {
            row["host"]: row["merged_points"]
            for row in rollup["hosts"]},
        "fleet_incidents_total": total_fleet_incidents,
        "incident_auto_resolved": resolved,
        "forecast": fc["signals"],
    }
    bench_common.emit_record(record, include_metrics=False)
    if failures:
        bench_common.log("load_harness fleet FAIL: "
                         + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness fleet PASS: both peers merged under host labels "
        f"{hosts_seen}, kill drill opened exactly one auto-resolving "
        f"{federation.INCIDENT_NAME}, forecast backtest rel err "
        f"{rel_err:.4f} (bar {forecast_err_bar:.4f})")
    return 0


def main() -> int:
    if os.environ.get("SPARKML_LOAD_PHASE") == "device_capacity_child":
        return device_capacity_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "ramp_child":
        return ramp_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "ramp":
        return run_ramp_phase()
    if os.environ.get("SPARKML_LOAD_PHASE") == "accounting_child":
        return accounting_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "accounting":
        return run_accounting_phase()
    if os.environ.get("SPARKML_LOAD_PHASE") == "fitmon_child":
        return fitmon_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "fitmon":
        return run_fitmon_phase()
    if os.environ.get("SPARKML_LOAD_PHASE") == "density_child":
        return density_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "density":
        return run_density_phase()
    if os.environ.get("SPARKML_LOAD_PHASE") == "fleet_child":
        return fleet_child()
    if os.environ.get("SPARKML_LOAD_PHASE") == "fleet":
        return run_fleet_phase()
    soak_s = _env_float("SPARKML_LOAD_SOAK_SECONDS", 60.0)
    calibrate_s = _env_float("SPARKML_LOAD_CALIBRATE_SECONDS", 8.0)
    n_features = _env_int("SPARKML_LOAD_FEATURES", 16)
    k = _env_int("SPARKML_LOAD_K", 8)
    greedy_threads = _env_int("SPARKML_LOAD_GREEDY_THREADS", 24)
    compliant_threads = _env_int("SPARKML_LOAD_COMPLIANT_THREADS", 4)
    min_availability = _env_float("SPARKML_LOAD_MIN_AVAILABILITY", 0.99)
    throughput_fraction = _env_float(
        "SPARKML_LOAD_THROUGHPUT_FRACTION", 0.9)
    # compliant p99 bar: explicit env wins; 0 (the default) derives it
    # from calibration — max(the serve latency SLO threshold, 2x the
    # single-tenant p99 at capacity). On a fast chip the SLO threshold
    # governs; on a slow shared-GIL CPU container the relative bar still
    # proves the fairness property (overload must not make the
    # protected tenant materially slower than the unloaded system).
    p99_bar_env = _env_float("SPARKML_LOAD_P99_MS", 0.0)

    import jax

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        ShedController,
        start_serve_server,
    )

    device = jax.devices()[0]
    rng = np.random.default_rng(17)
    x = rng.normal(size=(2048, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("load_pca", model)

    # -- phase 1: calibrate single-tenant capacity -------------------------
    bench_common.log("load_harness calibrate")
    cal_engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=2.0,
                             max_queue_depth=64)
    cal_engine.warmup("load_pca")
    cal_server = start_serve_server(cal_engine)
    cal_base = f"http://127.0.0.1:{cal_server.server_address[1]}"
    # Calibrate at the SOAK's total concurrency with a comparable size
    # mix — capacity measured at a different operating point is not a
    # capacity the soak's throughput can honestly be compared against.
    cal = TenantLoad(cal_base, "load_pca", x, tenant="calibrate",
                     priority="interactive",
                     threads=compliant_threads + greedy_threads,
                     pace_rps_per_thread=0.0, rows_lo=8, rows_hi=48,
                     seed=1)
    t0 = time.monotonic()
    cal.run(calibrate_s)
    cal_wall = time.monotonic() - t0
    cal_stats = cal.stats(cal_wall)
    cal_server.shutdown()
    cal_engine.shutdown()
    capacity_rows = max(cal_stats["served_rows_per_sec"], 1.0)
    p99_bar_ms = p99_bar_env if p99_bar_env > 0 else max(
        _env_float("SPARK_RAPIDS_ML_TPU_SLO_LATENCY_THRESHOLD_MS", 250.0),
        2000.0 * cal_stats["p99"])
    bench_common.log(
        f"load_harness capacity {capacity_rows:,.0f} rows/s "
        f"({cal_stats['offered_rps']:.0f} req/s), single-tenant p99 "
        f"{cal_stats['p99'] * 1000:.0f} ms -> compliant bar "
        f"{p99_bar_ms:.0f} ms")

    # -- phase 2: the 2x overload soak -------------------------------------
    # Work-conserving quota split from measured capacity: greedy is
    # PROVISIONED 45% and compliant 30% (offered ~25%) — the greedy
    # flood beyond its 45% is the over-quota excess the controller
    # sheds, so total served stays near capacity while the excess
    # absorbs every rejection.
    greedy_quota = max(capacity_rows * 0.45, 50.0)
    compliant_quota = max(capacity_rows * 0.30, 200.0)
    # The shed controller targets a FIXED queue wait (default 100 ms,
    # env SPARKML_LOAD_SHED_WAIT_MS) rather than a fraction of the p99
    # bar: the controller's job is to keep queueing bounded; the bar
    # only judges the outcome.
    shed = ShedController(
        queue_wait_target_s=_env_float(
            "SPARKML_LOAD_SHED_WAIT_MS", 100.0) / 1000.0,
        hold_seconds=1.0,
    )
    engine = ServeEngine(
        registry, max_batch_rows=256, max_wait_ms=2.0,
        max_queue_depth=64,
        tenant_quotas={
            "greedy": (greedy_quota, greedy_quota),
            "compliant": (compliant_quota, 2.0 * compliant_quota),
        },
        shed=shed,
    )
    engine.warmup("load_pca")
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"

    # compliant pacing: ~25% of capacity in rows/s → req/s at the mean
    # request size, split across its threads
    mean_rows = (4 + 16) / 2.0
    compliant_rps = max(capacity_rows * 0.25 / mean_rows, 1.0)
    compliant = TenantLoad(
        base, "load_pca", x, tenant="compliant", priority="interactive",
        threads=compliant_threads,
        pace_rps_per_thread=compliant_rps / max(compliant_threads, 1),
        rows_lo=4, rows_hi=16, seed=2)
    # Greedy request size auto-scales from calibration so the flood is
    # a genuine 2x+ overload REGARDLESS of how fast this machine is
    # today: a closed loop can only offer threads/latency requests per
    # second, so the rows-per-request must carry the excess. Factor 3.0
    # (was 2.2): the closed loop's request latency under overload runs
    # well past the CALIBRATION p50 this formula divides by, so the
    # realized offer undershoots the target — and after the PR 12 wire
    # wins lifted single-tenant capacity ~5x, 2.2 stopped clearing the
    # >= 1.5x offered gate on fast containers at all.
    closed_loop_rps = greedy_threads / max(cal_stats["p50"], 0.02)
    greedy_rows = int(min(max(
        3.0 * capacity_rows / max(closed_loop_rps, 1.0), 32), 176))
    greedy = TenantLoad(
        base, "load_pca", x, tenant="greedy", priority="batch",
        threads=greedy_threads, pace_rps_per_thread=0.0,
        rows_lo=max(greedy_rows // 2, 16),
        rows_hi=min(greedy_rows + greedy_rows // 2, 240),
        reject_pause_s=0.02, deadline_ms=3000.0, seed=3)

    bench_common.log(
        f"load_harness soak {soak_s:.0f}s (greedy quota "
        f"{greedy_quota:,.0f} rows/s, {greedy_threads} closed-loop "
        f"threads)")
    readyz_shedding_seen = False
    shed_level_max = 0

    def _watch_readyz(stop_at: float) -> None:
        nonlocal readyz_shedding_seen, shed_level_max
        while time.monotonic() < stop_at:
            doc = _get_json(base, "/readyz")
            if doc.get("status") == "shedding":
                readyz_shedding_seen = True
                shed_level_max = max(shed_level_max,
                                     int(doc.get("shed_level", 1)))
            time.sleep(0.5)

    stop_at = time.monotonic() + soak_s
    watcher = threading.Thread(target=_watch_readyz, args=(stop_at,),
                               daemon=True)
    watcher.start()
    t0 = time.monotonic()
    threads = [
        threading.Thread(target=compliant.run, args=(soak_s,),
                         daemon=True),
        threading.Thread(target=greedy.run, args=(soak_s,), daemon=True),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(soak_s + 120.0)
    wall = time.monotonic() - t0
    watcher.join(5.0)

    compliant_stats = compliant.stats(wall)
    greedy_stats = greedy.stats(wall)
    breakers = engine.breaker_snapshot()
    overload = engine.overload_state()
    slo_doc = _get_json(base, "/debug/slo")
    server.shutdown()
    engine.shutdown()
    # Let the background sampler/worker threads leave their jax calls
    # before interpreter teardown — a daemon thread mid-dispatch at exit
    # aborts the process AFTER the verdict (the chaos-drill lesson).
    from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

    tsdb_mod.get_sampler().stop()
    time.sleep(1.0)

    # -- phase 3: device scaling (ISSUE 13) --------------------------------
    device_scaling: dict = {}
    scaling_min = _env_float("SPARKML_LOAD_DEVICE_SCALING_MIN", 1.6)
    if _env_float("SPARKML_LOAD_DEVICE_SCALING", 1.0) > 0:
        device_scaling = run_device_scaling_phase()
        if "error" not in device_scaling:
            bench_common.log(
                f"load_harness device scaling: "
                f"{device_scaling['one_device']['capacity_rows_per_sec']:,.0f}"
                f" rows/s at 1 device -> "
                f"{device_scaling['two_devices']['capacity_rows_per_sec']:,.0f}"
                f" at 2 ({device_scaling['capacity_ratio']:.2f}x), "
                f"2-device p99 "
                f"{device_scaling['two_devices']['p99_ms']:.0f} ms vs "
                f"{device_scaling['p99_bar_ms']:.0f} ms bar")

    total_served = (compliant_stats["served_rows_per_sec"]
                    + greedy_stats["served_rows_per_sec"])
    total_offered = (compliant_stats["offered_rows_per_sec"]
                     + greedy_stats["offered_rows_per_sec"])
    breakers_closed = all(b["state"] == "closed"
                          for b in breakers.values()) if breakers else True
    record = {
        "bench": "load_harness",
        # the headline the sentinel judges: the fairness contract —
        # explicit direction, immune to unit-text heuristics
        "metric": "load_harness_compliant_availability",
        "value": compliant_stats["availability"],
        "unit": "fraction of compliant-tenant requests answered 200",
        "higher_is_better": True,
        "platform": device.platform,
        "device_kind": str(device.device_kind),
        "soak_seconds": wall,
        "capacity_rows_per_sec": capacity_rows,
        "offered_rows_per_sec": total_offered,
        "offered_over_capacity": (total_offered / capacity_rows
                                  if capacity_rows else 0.0),
        "served_rows_per_sec": total_served,
        "throughput_fraction": (total_served / capacity_rows
                                if capacity_rows else 0.0),
        "compliant": compliant_stats,
        "greedy": greedy_stats,
        "p50": compliant_stats["p50"],
        "p99": compliant_stats["p99"],
        "percentiles": {"p50": compliant_stats["p50"],
                        "p99": compliant_stats["p99"]},
        "calibrate_p50": cal_stats["p50"],
        "calibrate_p99": cal_stats["p99"],
        "p99_bar_ms": p99_bar_ms,
        "readyz_shedding_seen": readyz_shedding_seen,
        "shed_level_max": shed_level_max,
        "breakers_closed": breakers_closed,
        "device_scaling": device_scaling,
        "shed_snapshot": overload.get("shed", {}),
        "tenants": overload.get("tenants", {}),
        "slo_alerts_firing": len(slo_doc.get("alerts", [])),
    }
    bench_common.emit_record(record)

    failures = []
    if compliant_stats["availability"] < min_availability:
        failures.append(
            f"compliant availability {compliant_stats['availability']:.4f}"
            f" < {min_availability}")
    if compliant_stats["p99"] * 1000.0 > p99_bar_ms:
        failures.append(
            f"compliant p99 {compliant_stats['p99'] * 1000:.1f} ms > "
            f"{p99_bar_ms} ms bar")
    if record["throughput_fraction"] < throughput_fraction:
        failures.append(
            f"throughput {record['throughput_fraction']:.2f} of capacity "
            f"< {throughput_fraction}")
    min_offered = _env_float("SPARKML_LOAD_MIN_OFFERED", 1.5)
    if record["offered_over_capacity"] < min_offered:
        failures.append(
            f"offered load only {record['offered_over_capacity']:.2f}x "
            f"capacity < {min_offered}x — not an overload soak")
    if not breakers_closed:
        failures.append(
            "a circuit breaker opened under pure overload — overload "
            "must never read as backend failure")
    if compliant_stats["shed"] > 0:
        failures.append(
            f"{compliant_stats['shed']} compliant (in-quota interactive) "
            "requests were shed — the controller must never shed them")
    if compliant_stats["hung"] or greedy_stats["hung"]:
        failures.append(
            f"{compliant_stats['hung'] + greedy_stats['hung']} "
            "request(s) hung")
    if device_scaling:
        if "error" in device_scaling:
            failures.append(
                f"device-scaling phase broke: {device_scaling['error']}")
        else:
            if device_scaling["capacity_ratio"] < scaling_min:
                failures.append(
                    f"2-device capacity only "
                    f"{device_scaling['capacity_ratio']:.2f}x the "
                    f"1-device calibration < {scaling_min}x")
            if not device_scaling["p99_under_bar"]:
                failures.append(
                    f"2-device p99 "
                    f"{device_scaling['two_devices']['p99_ms']:.0f} ms "
                    f"over the single-device bar "
                    f"{device_scaling['p99_bar_ms']:.0f} ms")
            if device_scaling["two_devices"]["hung"] or \
                    device_scaling["one_device"]["hung"]:
                failures.append("device-scaling request(s) hung")
    if failures:
        bench_common.log("load_harness FAIL: " + "; ".join(failures))
        return 1
    bench_common.log(
        f"load_harness PASS: compliant availability "
        f"{compliant_stats['availability']:.4f} at "
        f"{record['offered_over_capacity']:.1f}x offered load, "
        f"throughput {record['throughput_fraction']:.2f}x capacity, "
        f"greedy availability {greedy_stats['availability']:.3f} "
        f"({greedy_stats['shed']} shed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
