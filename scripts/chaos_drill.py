#!/usr/bin/env python
"""Chaos drill: run the fault matrix against a live serve server.

Stands up the real stack — fitted PCA model, registry, engine with
retries + breaker + degraded CPU fallback, stdlib HTTP server — then
attacks it through the fault-injection plane (``serve.faults``), one
fault class at a time, measuring what a client on the wire experiences:

* **baseline**   — no faults: availability must be 1.0;
* **raise**      — 100% backend errors: the breaker opens, traffic
  degrades to the CPU fallback, availability stays high;
* **stall**      — a transform wedges past the worker budget: the
  watchdog fails it fast (``WorkerCrashed`` → 503), the worker
  restarts, traffic continues;
* **nan**        — corrupted outputs: the NaN guard converts poison
  into retryable errors;
* **latency**    — +spike on every call: answers stay correct, the SLO
  latency burn shows it;
* **overload**   — closed-loop 2x+ traffic from a greedy batch tenant
  with a tiny quota alongside a compliant interactive tenant (while a
  latency fault plays "the device is the bottleneck"): the compliant
  tenant keeps its availability, the adaptive controller sheds the
  greedy excess, the queue-depth detector opens (and auto-resolves) an
  incident, and the breaker stays CLOSED throughout — overload must
  never read as backend failure (the PR 6 invariant extended to the
  admission/shed layer);
* **recovery**   — faults cleared: a half-open probe closes the
  breaker and availability returns to 1.0;
* **canary_rollback** — train-while-serving (own subprocess): a
  streaming-fit candidate version canaries a slice of live alias
  traffic, a fault targeted at the CANDIDATE VERSION fires, the
  incumbent's traffic stays at availability 1.0, the rollout
  controller auto-rolls the alias back within the detector window,
  and exactly one ``serve_canary_regressed`` incident (labels naming
  the candidate version, complete bundle) opens and auto-resolves.

The drill also asserts the **auto-incident loop** (``obs.incidents``,
installed on the sampler by the serve server): each injected fault
class must open EXACTLY ONE deduped incident from its expected detector
(``raise``/``stall``/``nan`` → ``serve_error_rate``, ``latency`` →
``serve_p99_spike``) with an evidence bundle on disk (incident +
implicated-series history + a flight dump), and that incident must
auto-resolve after the fault clears. The drill compresses the loop via
env knobs set below (100 ms sampling cadence, 8 s detector windows,
1 s reopen cooldown) — the same engine, just faster.

Every request gets exactly one terminal outcome (the drill exits 1 if
any hangs past its client timeout, if availability under fault drops
below ``SPARKML_CHAOS_MIN_AVAILABILITY``, default 0.5, or if any
fault class fails its incident contract), and the drill emits ONE
``bench_common.emit_record`` line the perf sentinel can judge against
committed history:

* ``availability_baseline`` / ``availability_under_fault`` /
  ``availability_recovery`` — fraction of requests answered 200
  (degraded answers count: the service answered);
* ``degraded_served``       — how many answers came from the CPU
  fallback;
* ``breaker_open_seconds``  — how long the breaker was open during the
  drill (lower = faster recovery);
* ``recovery_seconds``      — fault cleared → breaker closed again;
* ``incidents_opened`` / ``incidents_resolved`` — auto-incident totals
  over the drill (opened counts everything the detectors saw,
  including cross-cutting ones like breaker flaps or SLO fast-burn).

CPU-only harness: the replica_drain, canary_rollback and autoscale_flap
phases run in subprocesses on forced host devices (CPU backend only),
pinned to ``JAX_PLATFORMS=cpu`` — a chip belongs to one process, and a
child that inherited ``tpu`` would collide with its parent.

Knobs (env): SPARKML_CHAOS_REQUESTS (per phase, default 24),
SPARKML_CHAOS_FEATURES (16), SPARKML_CHAOS_K (4).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

# Compress the detect→diagnose→resolve loop BEFORE the package is
# imported (the engine reads these at construction): 100 ms sampling
# into a 100 ms-resolution history tier (the default 1 s tier would
# quantize the 100 ms cadence right back to one point per second),
# 8 s detector windows, 2-sweep hysteresis, 1 s reopen cooldown, and no
# incident-triggered profile captures (the drill hammers the backend —
# a capture here would only add noise to the thing being measured).
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_SAMPLE_MS", "100")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_HISTORY",
                      "0.1x120,1x600")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_WINDOW_S", "8")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_OPEN_AFTER", "2")
os.environ.setdefault(
    "SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_RESOLVE_AFTER", "3")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_COOLDOWN_S", "1")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S", "0")
# The overload phase: the greedy tenant gets a deliberately tiny quota
# (closed-loop flood is ~10x over it) and the shed controller reacts to
# queue wait at drill scale. Other phases use the default tenant
# (interactive, unlimited quota), which the controller never sheds —
# these knobs change nothing for them.
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_SERVE_TENANT_QUOTAS",
                      "chaos_greedy:30:30")
os.environ.setdefault("SPARK_RAPIDS_ML_TPU_SERVE_SHED_QUEUE_WAIT_MS",
                      "200")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench_common  # noqa: E402 (scripts/ on path when run directly)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _post_predict(base: str, model: str, rows, timeout: float = 15.0,
                  tenant: str = "", priority: str = ""):
    """One HTTP predict; returns (status, payload_dict). Never raises —
    a drill request that cannot be categorized is itself a finding."""
    body = json.dumps({"model": model, "rows": rows.tolist()}).encode()
    headers = {"Content-Type": "application/json"}
    if tenant:
        headers["X-Tenant"] = tenant
    if priority:
        headers["X-Priority"] = priority
    req = urllib.request.Request(
        f"{base}/predict", data=body, headers=headers,
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        try:
            payload = json.loads(exc.read())
        except ValueError:
            payload = {}
        return exc.code, payload
    except Exception as exc:  # noqa: BLE001 - hang/reset IS the result
        return 0, {"error": f"{type(exc).__name__}: {exc}"}


def _get_json(base: str, path: str, timeout: float = 10.0) -> dict:
    try:
        resp = urllib.request.urlopen(f"{base}{path}", timeout=timeout)
        return json.loads(resp.read())
    except Exception:  # noqa: BLE001 - a dead ops endpoint IS a finding
        return {}


def _incident_entries(doc: dict, detector: str) -> list:
    return [i for i in (doc.get("open", []) + doc.get("recent", []))
            if i.get("detector") == detector]


def _await_new_incidents(base: str, detector: str, known_ids: set,
                         budget: float = 15.0) -> list:
    """Poll ``/debug/incidents`` until the detector grows a NEW
    incident (then one more beat to catch a dedup failure); returns
    every new entry seen."""
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        doc = _get_json(base, "/debug/incidents")
        new = [i for i in _incident_entries(doc, detector)
               if i.get("id") not in known_ids]
        if new:
            # one more detector cadence: continued firing must UPDATE
            # the incident, not open a sibling
            time.sleep(1.0)
            doc = _get_json(base, "/debug/incidents")
            return [i for i in _incident_entries(doc, detector)
                    if i.get("id") not in known_ids]
        time.sleep(0.2)
    return []


def _await_resolved(base: str, incident_id: str,
                    budget: float = 30.0) -> bool:
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        doc = _get_json(base, "/debug/incidents")
        for entry in doc.get("recent", []):
            if (entry.get("id") == incident_id
                    and entry.get("state") == "resolved"):
                return True
        time.sleep(0.2)
    return False


REPLICA_DRAIN_PREFIX = "REPLICA_DRAIN_RESULT "


def replica_drain_child() -> int:
    """The replica-drain drill leg, run in its OWN process with 2
    forced host devices (device count is fixed at jax init — the main
    drill stays a faithful single-device rehearsal).

    Contract (ISSUE 13): fault ONE device's replica → availability
    >= 0.99 via the surviving replica (retries + placement drain),
    exactly one ``serve_replica_degraded`` incident opens with a
    complete evidence bundle and auto-resolves, and the drained replica
    re-enters after its half-open probe succeeds."""
    import concurrent.futures

    import jax

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        fault_plane,
        start_serve_server,
    )

    result = {"devices": len(jax.devices())}
    rng = np.random.default_rng(13)
    x = rng.normal(size=(1024, 16))
    model = PCA().setK(4).fit(x)
    registry = ModelRegistry()
    registry.register("drill_replica_pca", model, buckets=(16, 64))
    # retries=3 covers the drain threshold (3): the ISSUE 15
    # small-request concentration pins the idle tier (and its retries)
    # to the SAME replica until its health trips, so the first faulted
    # request's surviving attempt is the fourth
    engine = ServeEngine(
        registry, max_batch_rows=64, max_wait_ms=1.0,
        retries=3, backoff_ms=10, breaker_failures=8,
        default_deadline_ms=10_000, replicas=2,
    )
    engine.warmup("drill_replica_pca")
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    plane = fault_plane()
    try:
        rset = engine._replicas[("drill_replica_pca", 1)]
        # replica 0: the concentration target — a fault targeted at the
        # spread-to sibling would never fire on light serial traffic
        victim = rset.replicas[0]
        victim.health.cooldown_seconds = 1.0
        result["victim_device"] = victim.label
        doc = _get_json(base, "/debug/incidents")
        known = {i.get("id") for i in
                 _incident_entries(doc, "serve_replica_degraded")}
        plane.inject("drill_replica_pca", "raise", count=None,
                     device=victim.label)

        statuses = []

        def one(i: int) -> None:
            n = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - n))
            status, _payload = _post_predict(
                base, "drill_replica_pca", x[start:start + n])
            statuses.append(status)

        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            list(pool.map(one, range(80)))
        ok = sum(1 for s in statuses if s == 200)
        result["requests"] = len(statuses)
        result["availability"] = ok / len(statuses)
        result["hung"] = sum(1 for s in statuses if s == 0)
        result["victim_state_under_fault"] = victim.state()
        result["breaker_state"] = engine.breaker_snapshot()[
            "drill_replica_pca"]["state"]

        new = _await_new_incidents(base, "serve_replica_degraded",
                                   known)
        result["incidents_opened"] = len(new)
        problems = []
        if len(new) != 1:
            problems.append(
                f"expected exactly 1 serve_replica_degraded incident, "
                f"saw {len(new)}")
        for incident in new:
            problems.extend(_bundle_problems(incident))

        # recovery: the fault clears, the half-open probe re-enters
        plane.clear()
        deadline = time.monotonic() + 20.0
        while (victim.state() != "serving"
               and time.monotonic() < deadline):
            time.sleep(0.2)
            n = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - n))
            _post_predict(base, "drill_replica_pca",
                          x[start:start + n])
        result["reentered"] = victim.state() == "serving"
        if not result["reentered"]:
            problems.append("drained replica never re-entered")
        resolved = all(
            _await_resolved(base, incident["id"]) for incident in new)
        result["incidents_resolved"] = resolved
        if new and not resolved:
            problems.append("replica incident did not auto-resolve")
        result["problems"] = problems
    finally:
        plane.clear()
        server.shutdown()
        engine.shutdown()
        from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

        tsdb_mod.get_sampler().stop()
        time.sleep(1.0)
    sys.stdout.write(REPLICA_DRAIN_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0 if not result.get("problems") else 1


AUTOSCALE_FLAP_PREFIX = "AUTOSCALE_FLAP_RESULT "


def autoscale_flap_child() -> int:
    """The autoscale anti-flap drill leg, run in its OWN process with 4
    forced host devices.

    Contract (ISSUE 15): under a load square-wave OSCILLATING faster
    than the hysteresis hold, the controller must not flap — no two
    scale actions land closer than the cooldown, every request keeps
    answering 200, the breaker stays closed, and a deliberate
    scale-down never opens a ``serve_replica_degraded`` incident (a
    retired replica is an operator decision, not a sick device —
    exactly the incident-dedup discipline the other phases keep)."""
    import jax

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        AutoscaleController,
        ModelRegistry,
        ServeEngine,
        fault_plane,
        start_serve_server,
    )

    result = {"devices": len(jax.devices())}
    rng = np.random.default_rng(31)
    x = rng.normal(size=(1024, 16))
    model = PCA().setK(4).fit(x)
    registry = ModelRegistry()
    registry.register("flap_pca", model, buckets=(64, 256))
    engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1.0,
                         max_queue_depth=256,
                         default_deadline_ms=15_000)
    engine.warmup("flap_pca")
    engine.scale_replicas(1)
    # the modeled per-batch device time that makes capacity
    # replica-bound (the multidevice phases' CPU-CI honesty device)
    fault_plane().inject("flap_pca", "latency", count=None,
                         seconds=0.04)
    controller = AutoscaleController(
        engine, min_replicas=1, max_replicas=4, interval_s=0.2,
        up_queue_wait_s=0.05, up_hold_s=0.4, down_hold_s=1.0,
        cooldown_s=2.0, down_queue_wait_s=0.03, down_occupancy=0.6,
    )
    controller.start()
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    statuses = []
    try:
        doc = _get_json(base, "/debug/incidents")
        known = {i.get("id") for i in
                 _incident_entries(doc, "serve_replica_degraded")}
        # the square wave: ~1.2 s SATURATING burst (6 closed-loop
        # threads of full-bucket requests — several times the
        # 1-replica capacity), ~1.2 s silence — a period far shorter
        # than down_hold + cooldown, so a naive controller would flap
        # every cycle
        import concurrent.futures

        lock = threading.Lock()

        def _burst_client(worker: int, edge: float) -> None:
            # per-task rng: shared numpy Generators across threads can
            # corrupt draws into bad request shapes (the _tenant_burst
            # lesson)
            wrng = np.random.default_rng(1000 + worker)
            while time.monotonic() < edge:
                start = int(wrng.integers(0, x.shape[0] - 256))
                status, _payload = _post_predict(
                    base, "flap_pca", x[start:start + 256],
                    timeout=30.0)
                with lock:
                    statuses.append(status)

        stop_at = time.monotonic() + 14.0
        burst = True
        cycle = 0
        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            while time.monotonic() < stop_at:
                edge = min(time.monotonic() + 1.2, stop_at)
                if burst:
                    cycle += 1
                    list(pool.map(
                        lambda w: _burst_client(w + 6 * cycle, edge),
                        range(6)))
                else:
                    time.sleep(max(edge - time.monotonic(), 0.0))
                burst = not burst
        ok = sum(1 for s in statuses if s == 200)
        result["requests"] = len(statuses)
        result["availability"] = ok / len(statuses) if statuses else 0.0
        result["hung"] = sum(1 for s in statuses if s == 0)
        history = controller.decision_history()
        actions = [h for h in history
                   if h["decision"] in ("scale_up", "scale_down")]
        gaps = [round(b["at"] - a["at"], 3)
                for a, b in zip(actions, actions[1:])]
        result["scale_actions"] = [
            {"decision": h["decision"], "from": h["from"],
             "to": h["to"]} for h in actions]
        result["action_gaps_s"] = gaps
        result["cooldown_s"] = controller.cooldown_s
        result["breaker_state"] = engine.breaker_snapshot().get(
            "flap_pca", {}).get("state", "closed")
        new = [i for i in _incident_entries(
            _get_json(base, "/debug/incidents"),
            "serve_replica_degraded") if i.get("id") not in known]
        result["replica_incidents"] = len(new)
        problems = []
        if not actions:
            problems.append(
                "the oscillating load never drove a single scale "
                "action — the phase did not exercise the controller")
        bad = [g for g in gaps if g < controller.cooldown_s - 0.05]
        if bad:
            problems.append(
                f"scale actions {bad} s apart — flapping faster than "
                f"the {controller.cooldown_s} s cooldown")
        if result["availability"] < 0.99:
            problems.append(
                f"availability {result['availability']:.3f} < 0.99 "
                "under the oscillating load")
        if result["hung"]:
            problems.append(f"{result['hung']} request(s) hung")
        if result["breaker_state"] != "closed":
            problems.append(
                "breaker opened under pure load oscillation")
        if new:
            problems.append(
                f"{len(new)} serve_replica_degraded incident(s) opened "
                "by deliberate scale-downs — retirement must never "
                "page as a sick device")
        result["problems"] = problems
    finally:
        fault_plane().clear()
        controller.stop()
        server.shutdown()
        engine.shutdown()
        from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

        tsdb_mod.get_sampler().stop()
        time.sleep(1.0)
    sys.stdout.write(AUTOSCALE_FLAP_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0 if not result.get("problems") else 1


def run_autoscale_flap_phase() -> dict:
    """Spawn the 4-device autoscale-flap child; returns its result (or
    a synthesized failure entry when the child broke)."""
    import subprocess

    env = dict(os.environ)
    env["SPARKML_CHAOS_PHASE"] = "autoscale_flap_child"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = bench_common.force_device_count_flags(4)
    env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=420,
    )
    result = bench_common.prefixed_result(proc.stdout,
                                          AUTOSCALE_FLAP_PREFIX)
    if result is None:
        return {"problems": [
            f"autoscale-flap child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-1500:]}"]}
    if proc.returncode != 0 and not result.get("problems"):
        result.setdefault("problems", []).append(
            f"autoscale-flap child exited {proc.returncode}")
    return result


CANARY_ROLLBACK_PREFIX = "CANARY_ROLLBACK_RESULT "


def canary_rollback_child() -> int:
    """The canary-rollback drill leg, run in its OWN process (fresh
    incident engine, fresh metrics, nothing shared with the main
    drill's detectors).

    Contract (ISSUE 14): stream-fit a candidate version while the
    incumbent serves, canary a slice of live alias traffic onto it,
    inject a fault targeted at the CANDIDATE VERSION ONLY → every
    incumbent-served request stays 200 (non-canary availability 1.0),
    the controller auto-rolls the alias back within the detector
    window, exactly one ``serve_canary_regressed`` incident opens with
    a complete evidence bundle whose labels name the candidate
    version, and the incident auto-resolves once the regressed gauge's
    hold elapses."""
    import concurrent.futures
    import tempfile

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        RolloutController,
        ServeEngine,
        StreamingTrainer,
        fault_plane,
        start_serve_server,
    )

    result = {}
    problems = []
    rng = np.random.default_rng(14)
    n_features, k = 12, 3
    x = rng.normal(size=(1024, n_features))
    incumbent_model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("canary_pca", incumbent_model, buckets=(16, 64))
    # The model-level breaker stays OUT of this phase's way (huge
    # failure threshold, burn trip disabled): the actuator under test
    # is the ROLLOUT controller — a canary storm must be answered by an
    # alias rollback, not by the incumbent's breaker opening.
    engine = ServeEngine(
        registry, max_batch_rows=64, max_wait_ms=1.0,
        retries=1, backoff_ms=5,
        breaker_failures=1000, breaker_burn_threshold=0,
        default_deadline_ms=10_000,
    )
    rollout = RolloutController(
        engine, "canary_pca", alias="canary_prod",
        fraction=0.35, shadow_tenant="canary_shadow",
        min_requests=8, window_s=30.0, eval_interval_s=0.1,
        burn_threshold=14.4, availability_target=0.99,
        regressed_hold_s=3.0,
    )
    engine.attach_rollout(rollout)
    rollout.promote(1)  # initial deploy: warm, then pin the alias
    trainer = StreamingTrainer(
        registry, "canary_pca", n_features, k,
        batches_per_version=4,
        artifact_dir=tempfile.mkdtemp(prefix="sparkml_canary_drill_"),
        rollout=rollout,
    )
    # live-traffic shape: the trainer streams the SAME distribution the
    # incumbent was fitted on, so the candidate is numerically honest —
    # the injected fault, not the model, is what burns the canary
    for i in range(4):
        trainer.feed(x[i * 256:(i + 1) * 256])
    result["candidate"] = rollout.candidate
    if rollout.candidate is None:
        problems.append("streaming trainer never published a candidate")
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    plane = fault_plane()
    try:
        doc = _get_json(base, "/debug/incidents")
        known = {i.get("id") for i in
                 _incident_entries(doc, "serve_canary_regressed")}
        rollout.start_canary()
        candidate = rollout.canary_version
        result["canary_version"] = candidate
        plane.inject("canary_pca", "raise", count=None,
                     version=candidate)

        import threading

        outcomes = []
        lock = threading.Lock()

        def one(i: int) -> None:
            # per-task generator: numpy Generators are not thread-safe,
            # and a corrupted draw could slice a bad request shape that
            # reads as an incumbent failure (the _tenant_burst lesson)
            local_rng = np.random.default_rng(2000 + i)
            n = int(local_rng.integers(1, 9))
            start = int(local_rng.integers(0, x.shape[0] - n))
            status, payload = _post_predict(
                base, "canary_prod", x[start:start + n])
            with lock:
                outcomes.append((status, payload.get("version")))

        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            list(pool.map(one, range(120)))

        incumbent_hits = [s for s, v in outcomes if v == 1]
        canary_hits = [s for s, v in outcomes if v == candidate]
        unattributed = [s for s, v in outcomes
                        if v not in (1, candidate)]
        result["requests"] = len(outcomes)
        result["incumbent_requests"] = len(incumbent_hits)
        result["canary_requests"] = len(canary_hits)
        result["canary_errors"] = sum(1 for s in canary_hits
                                      if s != 200)
        result["unattributed"] = len(unattributed)
        result["non_canary_availability"] = (
            sum(1 for s in incumbent_hits if s == 200)
            / len(incumbent_hits) if incumbent_hits else 0.0)
        if unattributed:
            problems.append(
                f"{len(unattributed)} response(s) carried no serving "
                "version (cannot attribute to an arm)")

        # rollback within the detector window: the controller judges at
        # its eval cadence as results stream in; give it a short grace
        # of trickle traffic in case the burst ended right at the floor
        deadline = time.monotonic() + 10.0
        while rollout.canary_active and time.monotonic() < deadline:
            n = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - n))
            _post_predict(base, "canary_prod", x[start:start + n])
            time.sleep(0.05)
        decisions = list(rollout.decisions)
        rollbacks = [d for d in decisions if d["action"] == "rollback"]
        result["rolled_back"] = bool(rollbacks)
        result["rollback_reason"] = (rollbacks[0].get("reason")
                                     if rollbacks else None)
        if not rollbacks:
            problems.append(
                "canary never auto-rolled back under a candidate-"
                "targeted 100% fault")
        alias_entry = registry.resolve_entry("canary_prod")
        result["alias_version_after"] = alias_entry.version
        if alias_entry.version != 1:
            problems.append(
                f"alias points at v{alias_entry.version} after "
                "rollback (expected the incumbent v1)")

        # post-rollback: ALL alias traffic serves the incumbent at
        # availability 1.0 (the fault is still armed — it targets the
        # candidate version, which no longer sees traffic)
        post = []
        for _ in range(30):
            n = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - n))
            status, payload = _post_predict(
                base, "canary_prod", x[start:start + n])
            post.append((status, payload.get("version")))
        result["post_rollback_availability"] = (
            sum(1 for s, _v in post if s == 200) / len(post))
        result["post_rollback_canary_hits"] = sum(
            1 for _s, v in post if v == candidate)
        if result["post_rollback_canary_hits"]:
            problems.append(
                "candidate still served alias traffic after rollback")

        new = _await_new_incidents(base, "serve_canary_regressed",
                                   known)
        result["incidents_opened"] = len(new)
        if len(new) != 1:
            problems.append(
                f"expected exactly 1 serve_canary_regressed incident, "
                f"saw {len(new)}")
        for incident in new:
            problems.extend(_bundle_problems(incident))
            named = str(incident.get("labels", {}).get("candidate"))
            if named != str(candidate):
                problems.append(
                    f"incident names candidate {named!r}, expected "
                    f"{candidate!r}")
        # the regressed gauge clears after its hold (ticked by rollout
        # polls), then the detector's quiet sweeps auto-resolve
        resolved = True
        for incident in new:
            inc_deadline = time.monotonic() + 30.0
            done = False
            while time.monotonic() < inc_deadline:
                _get_json(base, "/debug/rollout")  # ticks the hold
                if _await_resolved(base, incident["id"], budget=0.5):
                    done = True
                    break
            if not done:
                resolved = False
                problems.append(
                    f"{incident['id']} did not auto-resolve after the "
                    "regressed hold")
        result["incidents_resolved"] = resolved
        result["problems"] = problems
    finally:
        plane.clear()
        server.shutdown()
        engine.shutdown()
        from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

        tsdb_mod.get_sampler().stop()
        time.sleep(1.0)
    sys.stdout.write(CANARY_ROLLBACK_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0 if not result.get("problems") else 1


def run_canary_rollback_phase() -> dict:
    """Spawn the canary-rollback child; returns its result (or a
    synthesized failure entry when the child broke)."""
    import subprocess

    env = dict(os.environ)
    env["SPARKML_CHAOS_PHASE"] = "canary_rollback_child"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=420,
    )
    result = bench_common.prefixed_result(proc.stdout,
                                          CANARY_ROLLBACK_PREFIX)
    if result is None:
        return {"problems": [
            f"canary-rollback child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-1500:]}"]}
    if proc.returncode != 0 and not result.get("problems"):
        result.setdefault("problems", []).append(
            f"canary-rollback child exited {proc.returncode}")
    return result


def run_replica_drain_phase() -> dict:
    """Spawn the 2-device replica-drain child; returns its result (or
    a synthesized failure entry when the child broke)."""
    import subprocess

    env = dict(os.environ)
    env["SPARKML_CHAOS_PHASE"] = "replica_drain_child"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = bench_common.force_device_count_flags(2)
    env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=420,
    )
    result = bench_common.prefixed_result(proc.stdout,
                                          REPLICA_DRAIN_PREFIX)
    if result is None:
        return {"problems": [
            f"replica-drain child produced no result "
            f"(rc={proc.returncode}): {proc.stderr[-1500:]}"]}
    if proc.returncode != 0 and not result.get("problems"):
        result.setdefault("problems", []).append(
            f"replica-drain child exited {proc.returncode}")
    return result


def _bundle_problems(incident: dict) -> list:
    """What's missing from one incident's on-disk evidence bundle."""
    problems = []
    evidence = incident.get("evidence") or {}
    directory = evidence.get("dir")
    if not directory or not os.path.isdir(directory):
        return [f"no evidence dir ({directory!r})"]
    for fname in ("incident.json", "history.json"):
        path = os.path.join(directory, fname)
        if not os.path.isfile(path):
            problems.append(f"missing {fname}")
    history_path = os.path.join(directory, "history.json")
    if os.path.isfile(history_path):
        try:
            with open(history_path) as f:
                history = json.load(f)
            implicated = history.get("implicated", {})
            if not implicated.get("series"):
                problems.append("history.json has no implicated series")
            if implicated.get("metric") != incident.get("metric"):
                problems.append("history.json implicates the wrong metric")
        except ValueError:
            problems.append("history.json unparseable")
    dump_path = evidence.get("flight_dump")
    if not dump_path or not os.path.isfile(dump_path):
        problems.append(f"no flight dump ({dump_path!r})")
    return problems


def _phase(base: str, model: str, x, n_requests: int, rng):
    """Drive one phase; returns per-phase stats."""
    statuses = []
    degraded = 0
    hung = 0
    for _ in range(n_requests):
        n = int(rng.integers(1, 9))
        start = int(rng.integers(0, x.shape[0] - n))
        t0 = time.monotonic()
        status, payload = _post_predict(base, model, x[start:start + n])
        if status == 0:
            hung += 1
        if status == 200 and payload.get("degraded"):
            degraded += 1
        statuses.append(status)
        _ = time.monotonic() - t0
    ok = sum(1 for s in statuses if s == 200)
    return {
        "requests": n_requests,
        "ok": ok,
        "availability": ok / n_requests if n_requests else 0.0,
        "degraded": degraded,
        "hung": hung,
        "statuses": sorted(set(statuses)),
    }


def _concurrent_burst(base: str, model: str, x, n_requests: int, rng,
                      width: int = 4):
    """Drive one phase from ``width`` client threads at once, so the
    pipelined batcher genuinely holds batches in its in-flight window
    while the fault fires (the serial ``_phase`` loop rarely gets two
    batches in flight). Same stats shape as ``_phase``."""
    import threading

    jobs = [(int(rng.integers(1, 9)),
             int(rng.integers(0, x.shape[0] - 9)))
            for _ in range(n_requests)]
    results = []
    lock = threading.Lock()
    cursor = {"i": 0}

    def worker():
        while True:
            with lock:
                if cursor["i"] >= len(jobs):
                    return
                n, start = jobs[cursor["i"]]
                cursor["i"] += 1
            status, payload = _post_predict(base, model,
                                            x[start:start + n])
            with lock:
                results.append(
                    (status, bool(payload.get("degraded"))))

    threads = [threading.Thread(target=worker) for _ in range(width)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ok = sum(1 for s, _ in results if s == 200)
    return {
        "requests": n_requests,
        "ok": ok,
        "availability": ok / n_requests if n_requests else 0.0,
        "degraded": sum(1 for _, d in results if d),
        "hung": sum(1 for s, _ in results if s == 0),
        "statuses": sorted({s for s, _ in results}),
    }


def _tenant_burst(base: str, model: str, x, seconds: float,
                  greedy_width: int = 18, compliant_width: int = 4):
    """The overload phase's client fleet: a greedy batch-priority tenant
    flooding closed-loop from ``greedy_width`` threads (tiny quota → ~10x
    over it) alongside a compliant interactive tenant — per-tenant stats
    so the fairness contract is assertable from the wire."""
    import threading

    lock = threading.Lock()
    results = {"chaos_greedy": [], "chaos_compliant": []}
    seeds = iter(range(1000, 2000))
    stop_at = time.monotonic() + seconds

    def client(tenant: str, priority: str, seed: int):
        local_rng = np.random.default_rng(seed)
        while time.monotonic() < stop_at:
            n = int(local_rng.integers(4, 9))
            start = int(local_rng.integers(0, x.shape[0] - n))
            status, payload = _post_predict(
                base, model, x[start:start + n],
                tenant=tenant, priority=priority)
            with lock:
                results[tenant].append(
                    (status, bool(payload.get("shed")),
                     bool(payload.get("degraded"))))
            if status != 200:
                # bounded spin: a rejected closed-loop client hammering
                # at GIL speed would measure the client, not the server
                time.sleep(0.005)

    threads = [
        threading.Thread(target=client,
                         args=("chaos_greedy", "batch", next(seeds)),
                         daemon=True)
        for _ in range(greedy_width)
    ] + [
        threading.Thread(target=client,
                         args=("chaos_compliant", "interactive",
                               next(seeds)),
                         daemon=True)
        for _ in range(compliant_width)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 60.0)

    def stats(tenant: str) -> dict:
        rs = results[tenant]
        ok = sum(1 for s, _shed, _d in rs if s == 200)
        return {
            "requests": len(rs),
            "ok": ok,
            "availability": ok / len(rs) if rs else 0.0,
            "shed": sum(1 for s, shed, _d in rs if shed and s != 200),
            "degraded": sum(1 for s, _shed, d in rs
                            if d and s == 200),
            "hung": sum(1 for s, _shed, _d in rs if s == 0),
            "statuses": sorted({s for s, _shed, _d in rs}),
        }

    return {"greedy": stats("chaos_greedy"),
            "compliant": stats("chaos_compliant")}


def main() -> int:
    if os.environ.get("SPARKML_CHAOS_PHASE") == "replica_drain_child":
        return replica_drain_child()
    if os.environ.get("SPARKML_CHAOS_PHASE") == "canary_rollback_child":
        return canary_rollback_child()
    if os.environ.get("SPARKML_CHAOS_PHASE") == "autoscale_flap_child":
        return autoscale_flap_child()
    n_requests = _env_int("SPARKML_CHAOS_REQUESTS", 24)
    n_features = _env_int("SPARKML_CHAOS_FEATURES", 16)
    k = _env_int("SPARKML_CHAOS_K", 4)
    min_availability = float(
        os.environ.get("SPARKML_CHAOS_MIN_AVAILABILITY", 0.5))

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        fault_plane,
        start_serve_server,
    )

    rng = np.random.default_rng(13)
    x = rng.normal(size=(1024, n_features))
    model = PCA().setK(k).fit(x)

    registry = ModelRegistry()
    registry.register("chaos_pca", model, buckets=(16, 64))
    # worker budget 900 ms: far under the 2 s injected stall it must
    # catch, but WELL above the overload phase's worst case — a 150 ms
    # latency-faulted batch whose watchdog spans the depth-2 in-flight
    # window (~2 batch dispatches + a completion ≈ 0.35 s, plus GIL
    # noise). At 500 ms the overload phase read as a wedge storm and
    # the resulting WorkerCrashed failures opened the breaker — exactly
    # the "overload must never read as backend failure" confusion the
    # phase exists to rule out.
    engine = ServeEngine(
        registry, max_batch_rows=64, max_wait_ms=1.0,
        retries=2, backoff_ms=10,
        breaker_failures=3, breaker_cooldown_ms=400,
        worker_budget_ms=900, default_deadline_ms=10_000,
    )
    registry.warmup("chaos_pca")
    server = start_serve_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    plane = fault_plane()
    phases = {}
    incidents = {}
    incident_totals = {}
    breaker_open_at = None
    breaker_open_seconds = 0.0

    def breaker_state():
        snap = engine.breaker_snapshot().get("chaos_pca")
        return snap["state"] if snap else "closed"

    def _await_closed(budget: float = 30.0) -> float:
        """Drive probe traffic until the breaker closes (each fault
        class must start from a healthy state); returns how long it
        took."""
        t0 = time.monotonic()
        while (breaker_state() != "closed"
               and time.monotonic() < t0 + budget):
            time.sleep(0.1)
            n = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - n))
            _post_predict(base, "chaos_pca", x[start:start + n])
        return time.monotonic() - t0

    def _known_ids(detector: str) -> set:
        doc = _get_json(base, "/debug/incidents")
        return {i.get("id") for i in _incident_entries(doc, detector)}

    def _check_incident_loop(detector: str, known_ids: set,
                             exactly_one: bool = True) -> dict:
        """The auto-incident contract for one fault class: NEW
        incident(s) from the expected detector, each with a complete
        evidence bundle on disk, each auto-resolved after recovery.

        ``exactly_one`` (the error-class phases) additionally asserts
        the dedup contract — a square-wave fault burst must open ONE
        incident, continued firing updating it. The overload phase
        passes ``exactly_one=False``: a queue oscillating around the
        shed controller's equilibrium can legitimately resolve and
        re-open past the cooldown — the contract there is that the
        loop detects and closes, not that 8 s of oscillation is one
        square wave."""
        new = _await_new_incidents(base, detector, known_ids)
        result = {"detector": detector, "new_incidents": len(new)}
        bad_count = (len(new) != 1) if exactly_one else (len(new) < 1)
        if bad_count:
            expected = "exactly 1" if exactly_one else ">= 1"
            result["problems"] = [
                f"expected {expected} new {detector} incident(s), "
                f"saw {len(new)}"
            ]
            return result
        result["incident_id"] = new[0].get("id")
        problems = []
        resolved_all = True
        for incident in new:
            problems.extend(_bundle_problems(incident))
            if not _await_resolved(base, incident["id"]):
                resolved_all = False
                problems.append(
                    f"{incident['id']} did not auto-resolve after "
                    "recovery")
        result["resolved"] = resolved_all
        if problems:
            result["problems"] = problems
        else:
            bench_common.log(
                f"chaos incident loop OK: {detector} opened "
                f"{', '.join(i['id'] for i in new)} (bundle "
                f"{(new[0].get('evidence') or {}).get('dir')}) "
                "and auto-resolved")
        return result

    def _warm(n: int) -> None:
        """Healthy traffic right before an error-class storm: the
        error-rate detector judges the error FRACTION over its short
        window with a min-traffic floor, and by the time one fault
        class's incident has resolved (its errors aged out of the
        window) the previous phase's OK requests have aged out too —
        without fresh denominator traffic, three burst errors read as
        3/3 of nothing and the floor keeps the detector silent."""
        for _ in range(n):
            rows = int(rng.integers(1, 9))
            start = int(rng.integers(0, x.shape[0] - rows))
            _post_predict(base, "chaos_pca", x[start:start + rows])

    try:
        bench_common.log("chaos baseline")
        phases["baseline"] = _phase(base, "chaos_pca", x, n_requests, rng)

        # -- the storm: each fault class in turn, each from a healthy
        # breaker (otherwise the first class's open breaker routes every
        # later phase around the device and the later faults never
        # fire), and each awaited through its auto-incident loop so the
        # next error-class phase starts from a resolved detector (the
        # dedup/cooldown contract is per (detector, series)).
        #
        # latency runs FIRST: the p99 detector judges the CUMULATIVE
        # latency sketch, so the spike must land on a pristine tail —
        # after a raise/stall phase the retry+backoff stragglers have
        # already dragged p99 up and a further +50 ms cannot clear the
        # detector's min_step/min_relative guards against paging twice
        # on one regression.
        # +120 ms per call: the p99 detector needs a >= 2x jump over the
        # cumulative tail, and on a noisy shared-CPU container the
        # baseline p99 can already sit near 60-80 ms — a +50 ms spike
        # then reads as within-noise and the incident contract flakes.
        bench_common.log("chaos latency spike (+120ms per call)")
        known = _known_ids("serve_p99_spike")
        plane.inject("chaos_pca", "latency", count=None, seconds=0.12)
        phases["latency"] = _phase(base, "chaos_pca", x,
                                   max(n_requests // 2, 8), rng)
        plane.clear()
        incidents["latency"] = _check_incident_loop("serve_p99_spike",
                                                    known)

        bench_common.log("chaos raise storm (100% backend errors)")
        _warm(max(n_requests // 2, 12))
        known = _known_ids("serve_error_rate")
        plane.inject("chaos_pca", "raise", count=None)
        phases["raise"] = _phase(base, "chaos_pca", x, n_requests, rng)
        if breaker_state() != "closed":
            breaker_open_at = time.monotonic()
        plane.clear()
        opened_for = _await_closed()
        if breaker_open_at is not None:
            breaker_open_seconds += opened_for
        incidents["raise"] = _check_incident_loop("serve_error_rate",
                                                  known)

        bench_common.log("chaos stall (transform wedges past the budget)")
        _warm(max(n_requests // 2, 12))
        known = _known_ids("serve_error_rate")
        plane.inject("chaos_pca", "stall", count=3, seconds=2.0)
        phases["stall"] = _phase(base, "chaos_pca", x,
                                 max(n_requests // 2, 8), rng)
        plane.clear()
        _await_closed()
        incidents["stall"] = _check_incident_loop("serve_error_rate",
                                                  known)

        bench_common.log("chaos nan corruption")
        _warm(max(n_requests // 2, 12))
        known = _known_ids("serve_error_rate")
        plane.inject("chaos_pca", "nan", count=3)
        phases["nan"] = _phase(base, "chaos_pca", x,
                               max(n_requests // 2, 8), rng)
        plane.clear()
        _await_closed()
        incidents["nan"] = _check_incident_loop("serve_error_rate",
                                                known)

        # -- overload: closed-loop 2x+ capacity from a greedy tenant
        # with a tiny quota, alongside a compliant interactive tenant.
        # A +120 ms latency fault plays the role of "the device is the
        # bottleneck" so the queue genuinely builds at drill scale. The
        # invariants: the compliant tenant keeps its availability, the
        # queue-depth detector opens (and resolves) an incident, and
        # the breaker NEVER opens — overload and slowness are not
        # backend failure (the PR 6 invariant, extended to shedding).
        bench_common.log("chaos overload (2x closed-loop, mixed tenants)")
        _warm(max(n_requests // 2, 12))
        known = _known_ids("serve_queue_depth")
        # 150 ms per batch: deep enough queueing (22 closed-loop
        # clients vs ~10-request batches) that the depth detector sees
        # a sustained spike BEFORE the controller's queue-wait EWMA
        # crosses its 200 ms target and shedding drains the backlog —
        # while staying FAR under the 900 ms worker budget even across
        # the depth-2 in-flight window (a 300 ms fault span read as a
        # wedge storm under load, and WorkerCrashed opened the breaker
        # this phase exists to keep closed).
        plane.inject("chaos_pca", "latency", count=None, seconds=0.15)
        burst = _tenant_burst(base, "chaos_pca", x, 8.0)
        phases["overload_greedy"] = burst["greedy"]
        phases["overload_compliant"] = burst["compliant"]
        overload_breaker_state = breaker_state()
        plane.clear()
        incidents["overload"] = _check_incident_loop(
            "serve_queue_depth", known, exactly_one=False)
        # drain the shed level before the pipelined phases (quiet
        # signals de-escalate after the hold)
        time.sleep(2.5)

        # -- the pipelined drill: the same fault classes with batches
        # genuinely IN FLIGHT (concurrent clients + the async window,
        # PIPELINE_DEPTH default 2). The breaker/retry/incident
        # machinery must behave identically, and a worker restart must
        # leave no stuck in-flight window behind.
        bench_common.log(
            f"chaos pipelined latency (+20 ms, depth="
            f"{engine.pipeline_depth}, concurrent clients)")
        _warm(max(n_requests // 2, 12))
        plane.inject("chaos_pca", "latency", count=None, seconds=0.02)
        phases["pipelined_latency"] = _concurrent_burst(
            base, "chaos_pca", x, max(n_requests // 2, 8), rng)
        plane.clear()

        bench_common.log(
            "chaos pipelined stall (wedge mid-window -> restart)")
        plane.inject("chaos_pca", "stall", count=1, seconds=2.0)
        phases["pipelined_stall"] = _concurrent_burst(
            base, "chaos_pca", x, max(n_requests // 2, 8), rng)
        plane.clear()
        # no stuck in-flight window after the restart: the queue drains
        # and a fresh request answers once the breaker re-admits traffic
        t0 = time.monotonic()
        while engine.queue_depth() > 0 and time.monotonic() < t0 + 10:
            time.sleep(0.05)
        pipeline_stuck_window = engine.queue_depth() > 0
        _await_closed()
        status, _payload = _post_predict(base, "chaos_pca", x[:4])
        pipeline_recovered = status == 200
        # Let the abandoned wedged worker clear its 2 s stall and exit
        # cleanly BEFORE the drill ends: a daemon thread still inside a
        # jax call at interpreter teardown aborts the whole process
        # ("terminate called without an active exception") after the
        # verdict has already been decided.
        time.sleep(2.5)

        # -- recovery: wait out the cooldown, let a probe close it -------
        bench_common.log("chaos recovery (faults cleared)")
        recovery_seconds = _await_closed()
        phases["recovery"] = _phase(base, "chaos_pca", x, n_requests, rng)
        incident_totals = _get_json(base, "/debug/incidents")

        # -- replica drain: fault ONE device's replica (2-device
        # subprocess — device count is fixed at jax init) and prove the
        # placement tier sheds onto the sibling without taking the tier
        # down, with its own incident loop.
        bench_common.log("chaos replica drain (2-device subprocess)")
        replica_drain = run_replica_drain_phase()

        # -- canary rollback: stream-fit a candidate, canary it on live
        # alias traffic, fault ONLY the candidate version, and prove the
        # rollout tier rolls the alias back (own subprocess — fresh
        # incident engine, nothing shared with this drill's detectors).
        bench_common.log("chaos canary rollback (train-while-serving)")
        canary_rollback = run_canary_rollback_phase()

        # -- autoscale flap: an oscillating load square-wave must not
        # flap the replica controller faster than its hysteresis hold
        # (4-device subprocess, own incident engine).
        bench_common.log("chaos autoscale flap (4-device subprocess)")
        autoscale_flap = run_autoscale_flap_phase()
    finally:
        plane.clear()
        server.shutdown()
        engine.shutdown()
        # Stop the background sampler BEFORE interpreter teardown: a
        # daemon sweep mid-jax-call (devmon memory_stats) at
        # finalization aborts the process after the verdict.
        from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod

        tsdb_mod.get_sampler().stop()

    fault_phases = ("raise", "stall", "nan", "latency")
    fault_requests = sum(phases[p]["requests"] for p in fault_phases)
    fault_ok = sum(phases[p]["ok"] for p in fault_phases)
    # The pipelined phases get their OWN gate (not folded into
    # availability_under_fault, whose committed history predates them):
    # the behavior-parity claim is that faults with batches in flight
    # are no worse than the serial phases.
    availability_pipelined = min(
        phases[p]["availability"]
        for p in ("pipelined_latency", "pipelined_stall"))
    hung_total = sum(p["hung"] for p in phases.values())
    availability_under_fault = (fault_ok / fault_requests
                                if fault_requests else 0.0)
    record = {
        "bench": "chaos_drill",
        "availability_baseline": phases["baseline"]["availability"],
        "availability_under_fault": availability_under_fault,
        "availability_recovery": phases["recovery"]["availability"],
        "degraded_served": sum(p["degraded"] for p in phases.values()),
        "breaker_open_seconds": breaker_open_seconds,
        "recovery_seconds": recovery_seconds,
        "final_breaker_state": breaker_state(),
        "pipeline_depth": engine.pipeline_depth,
        "pipeline_stuck_window": pipeline_stuck_window,
        "pipeline_recovered": pipeline_recovered,
        "availability_pipelined": availability_pipelined,
        "availability_overload_compliant":
            phases["overload_compliant"]["availability"],
        "availability_overload_greedy":
            phases["overload_greedy"]["availability"],
        "overload_shed": phases["overload_greedy"]["shed"],
        "overload_breaker_state": overload_breaker_state,
        "incidents_opened": incident_totals.get("opened_total", 0),
        "incidents_resolved": incident_totals.get("resolved_total", 0),
        "incidents": incidents,
        "replica_drain": replica_drain,
        "availability_replica_drain": replica_drain.get(
            "availability", 0.0),
        "canary_rollback": canary_rollback,
        "availability_canary_incumbent": canary_rollback.get(
            "non_canary_availability", 0.0),
        "autoscale_flap": autoscale_flap,
        "availability_autoscale_flap": autoscale_flap.get(
            "availability", 0.0),
        "phases": {name: {k: v for k, v in stats.items()
                          if k != "statuses"}
                   for name, stats in phases.items()},
    }
    bench_common.emit_record(record)
    if hung_total:
        bench_common.log(f"chaos FAIL: {hung_total} request(s) hung")
        return 1
    if availability_under_fault < min_availability:
        bench_common.log(
            f"chaos FAIL: availability under fault "
            f"{availability_under_fault:.2f} < {min_availability}")
        return 1
    if record["final_breaker_state"] != "closed":
        bench_common.log("chaos FAIL: breaker did not close after recovery")
        return 1
    overload_min = float(
        os.environ.get("SPARKML_CHAOS_OVERLOAD_AVAILABILITY", 0.9))
    if record["availability_overload_compliant"] < overload_min:
        bench_common.log(
            f"chaos FAIL: compliant-tenant availability under overload "
            f"{record['availability_overload_compliant']:.2f} < "
            f"{overload_min}")
        return 1
    if record["overload_breaker_state"] != "closed":
        bench_common.log(
            "chaos FAIL: breaker opened during pure overload — "
            "shedding/slowness must never read as backend failure")
        return 1
    if availability_pipelined < min_availability:
        bench_common.log(
            f"chaos FAIL: pipelined-phase availability "
            f"{availability_pipelined:.2f} < {min_availability}")
        return 1
    if record["pipeline_stuck_window"]:
        bench_common.log(
            "chaos FAIL: in-flight window stuck after the pipelined "
            "worker restart (queue never drained)")
        return 1
    if not record["pipeline_recovered"]:
        bench_common.log(
            "chaos FAIL: no 200 answer after the pipelined stall "
            "restart + breaker recovery")
        return 1
    incident_failures = {name: check["problems"]
                         for name, check in incidents.items()
                         if check.get("problems")}
    if incident_failures:
        bench_common.log(
            f"chaos FAIL: incident loop broke for "
            f"{sorted(incident_failures)}: {incident_failures}")
        return 1
    replica_min = float(
        os.environ.get("SPARKML_CHAOS_REPLICA_AVAILABILITY", 0.99))
    if replica_drain.get("availability", 0.0) < replica_min:
        bench_common.log(
            f"chaos FAIL: replica-drain availability "
            f"{replica_drain.get('availability', 0.0):.3f} < "
            f"{replica_min} — the surviving replica did not absorb "
            "the faulted one")
        return 1
    if replica_drain.get("problems"):
        bench_common.log(
            f"chaos FAIL: replica-drain contract broke: "
            f"{replica_drain['problems']}")
        return 1
    if canary_rollback.get("non_canary_availability", 0.0) < 0.999:
        bench_common.log(
            f"chaos FAIL: non-canary availability "
            f"{canary_rollback.get('non_canary_availability', 0.0):.3f} "
            "< 1.0 — a candidate-targeted fault leaked onto the "
            "incumbent's traffic")
        return 1
    if canary_rollback.get("problems"):
        bench_common.log(
            f"chaos FAIL: canary-rollback contract broke: "
            f"{canary_rollback['problems']}")
        return 1
    if autoscale_flap.get("problems"):
        bench_common.log(
            f"chaos FAIL: autoscale-flap contract broke: "
            f"{autoscale_flap['problems']}")
        return 1
    bench_common.log("chaos drill PASS")
    # final settle: any worker abandoned mid-jax-call must leave the
    # call before interpreter teardown, or the process aborts AFTER the
    # verdict ("terminate called without an active exception")
    time.sleep(1.5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
