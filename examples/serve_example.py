"""Serving-engine walkthrough: registry → warmup → mixed-size traffic.

Fits a PCA model, registers it (with an alias, the way traffic would
address it), warms its shape buckets so XLA compiles happen at deploy
time, then drives 200 mixed-size predict requests through the engine
from a small thread pool and prints what the serving telemetry saw:
batch occupancy, padding waste, queue depth, deadline sheds, the
sketch-backed p50/p95/p99 — all read back from the live registry
snapshot — plus one request's ASSEMBLED trace tree (server → queue →
fan-in batch → transform, Dapper-style), a 60-sample queue-depth /
p99-latency HISTORY from the embedded time-series store (``obs.tsdb``
sampling in the background while traffic ran), and the run's SLO
verdict (burn rates per window, budget remaining, firing alerts) —
then the AUTO-INCIDENT loop: a latency fault is injected, the anomaly
detectors notice the p99 jump, an incident opens with an evidence
bundle on disk, and it auto-resolves after the fault clears — and
finally the MULTI-DEVICE serving tier: the same model replicated onto
both (forced) host devices, concurrent traffic split by least-loaded
placement, the per-device batch split printed from the replica
counters, a device-targeted fault draining one replica onto its
sibling, and an oversize request served by the batch-sharded program — and
closes with the LIVE ROLLOUT loop (``serve.rollout``): a streaming
trainer publishes a candidate version from live batches, a canary
routes 40% of alias traffic onto it under a shadow tenant, an injected
candidate-targeted fault regresses it, and the controller rolls the
alias back to the incumbent on its own — then the zero-cold-start
restart, the live ``/debug/costs`` rollup, and the TIERING finale: an
idle model driven COLD under a tight HBM budget, its next request
gated in admission and reactivated with zero fresh XLA compiles, the
tiering state table printed at each step.
Runs on CPU (JAX_PLATFORMS=cpu) or any accelerator.
"""

import concurrent.futures
import os
import sys
import time

# The multi-device demo needs >= 2 devices; on a CPU host that means
# forcing virtual host devices BEFORE the first jax import (device
# count is fixed at backend init). Appended, so an operator's own
# XLA_FLAGS survive; skipped when a forced count is already set.
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()

import numpy as np

# runnable from anywhere: put the repo root ahead of the script dir
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.obs import (
    assemble_trace,
    latency_quantiles,
    new_context,
    tracectx,
)
from spark_rapids_ml_tpu.obs import tsdb
from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine

BUCKETS = (32, 64, 128, 256)

SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def sparkline(values):
    """A terminal sparkline over the samples (▁▂▃▄▅▆▇█)."""
    if not values:
        return "(no samples)"
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    return "".join(
        SPARK_BLOCKS[int((v - lo) / span * (len(SPARK_BLOCKS) - 1))]
        for v in values
    )


def main():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(4096, 64))

    # Sample the registry into a fine-grained history store while the
    # example runs: 20 ms resolution so a few seconds of traffic yields
    # a dense queue-depth / latency timeline (the serve server does this
    # automatically via tsdb.start_sampling() at the 1 s default).
    hist_store = tsdb.TimeSeriesStore(tiers=((0.02, 120.0), (1.0, 600.0)))
    hist_sampler = tsdb.MetricsSampler(hist_store, interval_seconds=0.02)
    hist_sampler.start()

    print("== fit + register ==")
    model = PCA().setK(8).fit(x)
    registry = ModelRegistry()
    version = registry.register("pca_embedder", model, buckets=BUCKETS)
    registry.alias("prod", "pca_embedder")
    print(f"registered pca_embedder v{version}, alias 'prod', "
          f"buckets {BUCKETS}")

    print("\n== warmup (compiles happen HERE, not on user traffic) ==")
    engine = ServeEngine(registry, max_batch_rows=256, max_wait_ms=3,
                         buckets=BUCKETS)
    # engine.warmup = the registry's sync ladder PLUS the pipelined
    # batcher's precision x bucket ladder (ServingProgram variants)
    report = engine.warmup("prod")
    for bucket, seconds in sorted(report["buckets"].items()):
        print(f"  bucket {bucket:>4} rows: {seconds * 1000:7.1f} ms")
    pipeline = report.get("pipeline")
    if pipeline:
        print(f"  pipeline ladder ({pipeline['precision']}, depth "
              f"{engine.pipeline_depth}): "
              + ", ".join(f"{b}:{s * 1000:.0f}ms"
                          for b, s in sorted(pipeline["buckets"].items())))

    print("\n== 200 mixed-size requests through the engine ==")
    # sizes/offsets precomputed: numpy Generators are not thread-safe
    sizes = rng.integers(1, 200, size=200)
    starts = [int(rng.integers(0, x.shape[0] - int(n))) for n in sizes]

    # one request runs under an explicit TraceContext so we can pull its
    # assembled tree afterwards (header-less requests mint their own)
    tracked_ctx = new_context(example="serve_example")

    def one(i):
        n = int(sizes[i])
        if i == 100:
            with tracectx.activate(tracked_ctx):
                return engine.predict(
                    "prod", x[starts[i]:starts[i] + n]).shape
        return engine.predict("prod", x[starts[i]:starts[i] + n]).shape

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        shapes = list(pool.map(one, range(200)))
    wall = time.perf_counter() - t0
    engine.shutdown()
    total_rows = int(sizes.sum())
    print(f"served 200 requests / {total_rows} rows in {wall:.2f}s "
          f"({total_rows / wall:,.0f} rows/s); "
          f"first shapes: {shapes[:3]}")

    print("\n== what the live registry snapshot saw ==")
    snap = registry.snapshot()
    metrics = snap["metrics"]

    def scalar(name, label_value, default=0.0):
        for sample in metrics.get(name, {}).get("samples", []):
            if sample["labels"].get("model") == label_value:
                return sample["value"]
        return default

    batches = scalar("sparkml_serve_batches_total", "pca_embedder")
    real = scalar("sparkml_serve_batch_rows_total", "pca_embedder")
    bucket = scalar("sparkml_serve_bucket_rows_total", "pca_embedder")
    print(f"  batches executed:      {batches:.0f} "
          f"(coalesced from 200 requests)")
    print(f"  mean batch occupancy:  {real / bucket:.1%}" if bucket
          else "  mean batch occupancy:  n/a")
    print(f"  mean padding waste:    {1 - real / bucket:.1%}" if bucket
          else "")
    print(f"  queue depth now:       "
          f"{scalar('sparkml_serve_queue_depth', 'pca_embedder'):.0f}")
    print(f"  deadline sheds:        "
          f"{scalar('sparkml_serve_deadline_expired_total', 'pca_embedder'):.0f}")
    q = latency_quantiles("pca")  # the model-level transform sketch
    print(f"  transform p50/p95/p99: "
          f"{q['p50'] * 1e3:.1f} / {q['p95'] * 1e3:.1f} / "
          f"{q['p99'] * 1e3:.1f} ms")

    # The hot-path pipeline's phase split: the last batch's
    # TransformReport attributes stage (pad + host->device transfer),
    # dispatch (async launch) and sync (the completion-step host sync)
    # separately, and the busy/overlap counters show how much of the
    # wall-clock the in-flight window kept the device fed.
    from spark_rapids_ml_tpu.obs import last_transform_report

    pipe_report = last_transform_report("pca")
    if pipe_report and "stage" in (pipe_report.phases or {}):
        ph = pipe_report.phases
        print(f"  pipeline phase split:  stage {ph['stage'] * 1e3:.2f} / "
              f"dispatch {ph['dispatch'] * 1e3:.2f} / "
              f"sync {ph.get('sync', 0.0) * 1e3:.2f} ms (last batch)")
    busy = scalar("sparkml_serve_device_busy_seconds_total",
                  "pca_embedder")
    overlap2 = scalar("sparkml_serve_pipeline_overlap_seconds_total",
                      "pca_embedder")
    print(f"  pipeline overlap:      device busy {busy / wall:.0%} of "
          f"wall, >=2 batches in flight {overlap2 / wall:.0%}")
    names = [f"{m}@{versions[-1]['version']}"
             for m, versions in snap["models"].items()]
    print(f"  registered models:     {names}")

    print("\n== 60-sample history from the embedded tsdb ==")
    hist_sampler.stop()

    def last_points(name, labels=None):
        series = hist_store.range_query(name, labels, window=120.0)
        return series[0]["points"][-60:] if series else []

    qd = last_points("sparkml_serve_queue_depth",
                     {"model": "pca_embedder"})
    p99 = last_points("sparkml_serve_request_latency_seconds",
                      {"quantile": "0.99"})
    print(f"  sampler: {hist_sampler.sweeps} sweeps at "
          f"{hist_sampler.interval_seconds * 1000:.0f} ms, "
          f"{hist_store.series_count()} series")
    if qd:
        vals = [v for _ts, v in qd]
        print(f"  queue depth  ({len(vals)} samples, "
              f"min {min(vals):.0f} max {max(vals):.0f}):")
        print(f"    {sparkline(vals)}")
    if p99:
        vals = [v * 1e3 for _ts, v in p99]
        print(f"  p99 latency  ({len(vals)} samples, "
              f"min {min(vals):.1f} ms max {max(vals):.1f} ms):")
        print(f"    {sparkline(vals)}")
    req_rate = hist_store.rate("sparkml_serve_requests_total",
                               window=120.0)
    delta = hist_store.delta("sparkml_serve_requests_total",
                             window=120.0)
    print(f"  request counter: delta {delta:.0f} over the window "
          f"(rate {req_rate:.0f}/s) — reset-aware counter math over "
          f"the sampled cumulative series")

    print("\n== one request, followed across every seam ==")
    tree = assemble_trace(tracked_ctx.trace_id)

    def show(node, indent=1):
        extra = ""
        if node.get("links"):
            extra = f"  (fan-in: links {len(node['links'])} traces)"
        elif node.get("link"):
            extra = "  (shared batch subtree)"
        print(f"{'  ' * indent}{node['name']:<28}"
              f"{node['duration_ms']:9.3f} ms{extra}")
        for child in node["children"]:
            show(child, indent + 1)

    print(f"  trace {tracked_ctx.trace_id} "
          f"({tree['span_count']} spans):")
    for root in tree["spans"]:
        show(root)

    print("\n== SLO verdict (obs.slo, fed by every predict) ==")
    verdict = engine.slo_snapshot()
    for slo in verdict["slos"]:
        rates = "  ".join(f"{w}={r:.2f}"
                          for w, r in slo["burn_rates"].items())
        print(f"  {slo['name']:<20} target {slo['target']}: "
              f"burn {rates}")
        print(f"  {'':<20} budget remaining "
              f"{slo['budget_remaining']:.1%}")
    alerts = verdict["alerts"]
    print(f"  firing alerts:       "
          f"{[a['severity'] for a in alerts] if alerts else 'none'}")

    print("\n== fused whole-pipeline serving (one XLA program for "
          "scaler -> PCA -> classifier) ==")
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models._serving import run_staged_pipeline
    from spark_rapids_ml_tpu.models.logistic_regression import (
        LogisticRegression,
    )
    from spark_rapids_ml_tpu.models.pipeline import Pipeline
    from spark_rapids_ml_tpu.models.scaler import StandardScaler

    y = (x[:, 0] + 0.3 * x[:, 1] > 0).astype(float)
    pipe_model = Pipeline(stages=[
        StandardScaler().setWithMean(True).setOutputCol("scaled"),
        PCA().setK(8).setInputCol("scaled").setOutputCol("reduced"),
        LogisticRegression().setInputCol("reduced").setLabelCol("label"),
    ]).fit(VectorFrame({"features": x, "label": list(y)}))
    registry.register("pipe", pipe_model, buckets=BUCKETS)
    engine_p = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1,
                           buckets=BUCKETS)
    report_p = engine_p.warmup("pipe")
    fused_info = report_p.get("pipeline")
    print(f"  3 stages fused into ONE program per bucket "
          f"(ladder: {sorted((fused_info or {}).get('buckets', {}))}); "
          f"a pipelined predict pays one dispatch/complete cycle, "
          f"not three")
    fused_out = engine_p.predict("pipe", x[:16])
    staged_out = run_staged_pipeline(pipe_model, x[:16])
    print(f"  fused output bit-equal to the staged per-stage loop: "
          f"{np.array_equal(fused_out, staged_out)}")
    engine_p.shutdown()

    print("\n== binary columnar wire format (serve.wire) ==")
    import http.client
    import json as _json

    from spark_rapids_ml_tpu.serve import wire
    from spark_rapids_ml_tpu.serve.server import start_serve_server

    engine_w = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1,
                           buckets=BUCKETS)
    server_w = start_serve_server(engine_w)
    conn = http.client.HTTPConnection(
        "127.0.0.1", server_w.server_address[1])
    wire_rows = x[:128]
    for _ in range(20):  # enough parses for a meaningful split
        conn.request(
            "POST", "/predict",
            _json.dumps({"model": "prod", "rows": wire_rows.tolist()}),
            {"Content-Type": "application/json"})
        conn.getresponse().read()
        conn.request("POST", "/predict",
                     wire.encode_request("prod", wire_rows),
                     {"Content-Type": wire.BINARY_CONTENT_TYPE})
        resp = conn.getresponse()
        binary_outputs = wire.decode_response(resp.read())
    conn.close()
    jq = wire.parse_quantiles("json")
    bq = wire.parse_quantiles("binary")
    print(f"  one binary request: {len(wire_rows)} rows -> "
          f"{binary_outputs.shape} outputs "
          f"(Content-Type {wire.BINARY_CONTENT_TYPE})")
    print(f"  parse-phase split (p50/p99): "
          f"json {jq['p50'] * 1e3:.3f}/{jq['p99'] * 1e3:.3f} ms vs "
          f"binary {bq['p50'] * 1e3:.3f}/{bq['p99'] * 1e3:.3f} ms "
          f"({jq['p99'] / bq['p99']:.0f}x less time in the protocol)")
    server_w.shutdown()
    engine_w.shutdown()

    print("\n== multi-tenant fairness: greedy flood vs compliant "
          "tenant (closed-loop burst) ==")
    from spark_rapids_ml_tpu.serve import ShedController, ShedLoad

    # a greedy batch tenant with a deliberately tiny quota floods from
    # 4 closed-loop threads while a compliant interactive tenant keeps
    # a steady trickle; the shed controller (aggressive queue-wait
    # target so the demo bites within a few seconds) sheds the greedy
    # excess and the weighted-fair queue keeps the compliant tenant
    # served — the load_harness proves the same contract for 60 s over
    # real HTTP.
    engine_f = ServeEngine(
        registry, max_batch_rows=64, max_wait_ms=1, buckets=(16, 64),
        retries=0,
        tenant_quotas={"greedy": (50.0, 50.0)},
        shed=ShedController(queue_wait_target_s=0.01,
                            hold_seconds=0.5),
    )
    import threading as _threading

    counts = {"greedy": {"ok": 0, "shed": 0},
              "compliant": {"ok": 0, "shed": 0}}
    counts_lock = _threading.Lock()
    stop_burst = _threading.Event()

    def greedy_client(seed):
        local = np.random.default_rng(seed)
        while not stop_burst.is_set():
            i = int(local.integers(0, 512))
            try:
                engine_f.predict("prod", x[i:i + 16], tenant="greedy",
                                 priority="batch")
                outcome = "ok"
            except ShedLoad:
                outcome = "shed"
            except Exception:
                outcome = "shed"
            with counts_lock:
                counts["greedy"][outcome] += 1

    burst_threads = [_threading.Thread(target=greedy_client, args=(s,),
                                       daemon=True) for s in range(4)]
    for t in burst_threads:
        t.start()
    compliant_latencies = []
    for i in range(40):
        t1 = time.perf_counter()
        try:
            engine_f.predict("prod", x[i:i + 4], tenant="compliant",
                             priority="interactive")
            with counts_lock:
                counts["compliant"]["ok"] += 1
            compliant_latencies.append(time.perf_counter() - t1)
        except ShedLoad:
            with counts_lock:
                counts["compliant"]["shed"] += 1
        time.sleep(0.02)
    stop_burst.set()
    for t in burst_threads:
        t.join(5.0)
    overload = engine_f.overload_state()
    for tenant in ("compliant", "greedy"):
        c = counts[tenant]
        total = c["ok"] + c["shed"]
        availability = c["ok"] / total if total else 0.0
        print(f"  {tenant:<10} served {c['ok']:>4} shed {c['shed']:>4} "
              f"-> availability {availability:.3f}")
    if compliant_latencies:
        compliant_latencies.sort()
        print(f"  compliant p50 "
              f"{compliant_latencies[len(compliant_latencies) // 2] * 1e3:.1f} ms "
              f"while the greedy flood absorbed the shedding")
    print(f"  shed level now: {overload['shed']['level']} "
          f"(signals {overload['shed']['signals']}); "
          f"greedy quota tokens: "
          f"{overload['tenants'].get('greedy', {}).get('tokens')}")
    engine_f.shutdown()

    print("\n== injected outage -> breaker opens -> degraded CPU "
          "fallback -> recovery ==")
    from spark_rapids_ml_tpu.serve import fault_plane

    engine2 = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1,
                          buckets=BUCKETS, retries=1, backoff_ms=5,
                          breaker_failures=3, breaker_cooldown_ms=300)
    plane = fault_plane()
    plane.inject("pca_embedder", "raise", count=None)  # 100% device errors

    def state():
        return engine2.breaker_snapshot()["pca_embedder"]["state"]

    served_degraded = errored = 0
    for i in range(8):
        try:
            r = engine2.predict_detailed("prod", x[i:i + 8])
            if r.degraded:
                served_degraded += 1
                # bit-identical to the direct CPU projection
                assert np.array_equal(r.outputs, x[i:i + 8] @ model.pc)
        except Exception as exc:  # noqa: BLE001 - pre-open failures
            errored += 1
            print(f"  request {i}: {type(exc).__name__} "
                  f"(breaker {state()})")
    print(f"  outage: {errored} errored before the breaker opened, then "
          f"{served_degraded} served DEGRADED from the CPU path "
          f"(bit-checked) — breaker {state()}")

    plane.clear()                       # "the device backend recovers"
    time.sleep(0.35)                    # wait out the cooldown
    r = engine2.predict_detailed("prod", x[:8])
    print(f"  fault cleared: half-open probe served degraded={r.degraded} "
          f"-> breaker {state()}")
    engine2.shutdown()

    print("\n== auto-incident: latency fault -> detector -> evidence "
          "bundle -> auto-resolve ==")
    from spark_rapids_ml_tpu.obs import anomaly, incidents

    # The serve HTTP server installs this engine on the process sampler
    # automatically; here we drive the same pipeline by hand at a fast
    # cadence so the whole loop fits in a few seconds of wall clock.
    inc_engine = incidents.IncidentEngine(
        store=hist_store,
        detectors=anomaly.builtin_detectors(short_window=3.0),
        manager=incidents.IncidentManager(
            open_after=2, resolve_after=4, cooldown_seconds=1.0,
            capture_seconds=0.0,
        ),
    )
    inc_sampler = tsdb.MetricsSampler(hist_store, interval_seconds=0.02)
    inc_engine.install(inc_sampler)

    engine3 = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1,
                          buckets=BUCKETS)
    for i in range(10):  # baseline points at this cadence
        engine3.predict("prod", x[i:i + 8])
        inc_sampler.sample_once()
    # +400 ms per call: the earlier queue-heavy traffic put the
    # cumulative p99 around ~100 ms, and the rate-of-change detector
    # (rightly) only pages on a >= 2x jump
    plane.inject("pca_embedder", "latency", count=None, seconds=0.4)
    incident = None
    for i in range(40):
        engine3.predict("prod", x[i % 128:i % 128 + 8])
        inc_sampler.sample_once()
        opens = inc_engine.manager.open_incidents()
        if opens:
            incident = opens[0]
            break
    if incident is None:
        print("  (no incident opened — try again on a quieter machine)")
    else:
        ev = incident["evidence"]
        print(f"  incident {incident['id']} [{incident['severity']}] "
              f"opened by {incident['detector']}")
        print(f"    {incident['reason']}")
        print(f"    evidence bundle: {ev.get('dir')}")
        if ev.get("dir") and os.path.isdir(ev["dir"]):
            print(f"    bundle files:    {sorted(os.listdir(ev['dir']))}")
        print(f"    flight dump:     {ev.get('flight_dump')}")
    plane.clear()                       # the latency fault recovers
    t0 = time.monotonic()
    while incident is not None and time.monotonic() - t0 < 12.0:
        engine3.predict("prod", x[:8])
        inc_sampler.sample_once()
        if not inc_engine.manager.open_incidents():
            snap = inc_engine.snapshot()
            done = snap["recent"][0]
            print(f"  fault cleared: incident auto-resolved after "
                  f"{done['duration_seconds']:.1f}s "
                  f"({done['updates']} updates while open)")
            break
        time.sleep(0.05)
    engine3.shutdown()

    # -- multi-device serving: replicas, placement, drain, sharding ----
    import jax

    from spark_rapids_ml_tpu.obs import get_registry
    from spark_rapids_ml_tpu.serve.placement import serving_devices

    print("\n== multi-device serving tier (serve/placement.py) ==")
    devices = serving_devices()
    print(f"  visible devices: {[str(d) for d in devices]}")
    if len(devices) < 2:
        print("  (single device — run with XLA_FLAGS="
              "--xla_force_host_platform_device_count=2 for the demo)")
        return
    engine4 = ServeEngine(registry, max_batch_rows=256, max_wait_ms=1,
                          buckets=BUCKETS, replicas=len(devices))
    report = engine4.warmup("prod")
    print(f"  warmup staged the bucket ladder on "
          f"{len(report.get('replicas', {1: 1}))} device(s); sharded "
          f"program warmed at bucket "
          f"{report.get('sharded', {}).get('bucket', '—')}")

    def _split() -> dict:
        samples = get_registry().snapshot()[
            "sparkml_serve_replica_batches_total"]["samples"]
        return {s["labels"]["device"]: int(s["value"]) for s in samples
                if s["labels"]["model"] == "pca_embedder"}

    before = _split()
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(
            lambda i: engine4.predict("prod", x[i % 128:i % 128 + 16]),
            range(120)))
    split = {dev: count - before.get(dev, 0)
             for dev, count in _split().items()}
    total = sum(split.values()) or 1
    print("  per-device batch split over 120 concurrent requests:")
    for device_label, batches in sorted(split.items()):
        bar = "#" * int(30 * batches / total)
        print(f"    {device_label:<14} {batches:>4} batches  {bar}")

    # drain: fault ONE replica's device — traffic sheds onto the
    # sibling (retries absorb the failures; availability holds).
    # Concurrent clients, so the least-loaded pick keeps exercising
    # both replicas until the victim's health trips.
    rset = engine4._replicas[("pca_embedder", 1)]
    victim = rset.replicas[1]
    victim.health.cooldown_seconds = 1.0
    spec = plane.inject("pca_embedder", "raise", count=None,
                        device=victim.label)
    with concurrent.futures.ThreadPoolExecutor(6) as pool:
        served = [r is not None for r in pool.map(
            lambda i: engine4.predict("prod", x[i:i + 8]), range(48))]
    doc = engine4.replica_snapshot()["pca_embedder@1"]
    print(f"  device-targeted fault on {victim.label}: "
          f"{sum(served)}/48 served (the fault fired {spec.fired}x, "
          f"every one absorbed by retries + the sibling); replica "
          f"states now "
          f"{[(r['device'], r['state']) for r in doc['replicas']]}")
    plane.clear()
    time.sleep(1.1)
    for i in range(10):
        engine4.predict("prod", x[i:i + 8])
    print(f"  fault cleared: half-open probe re-entered the replica -> "
          f"{victim.state()}")

    # one HUGE request: above max_batch_rows it routes to the
    # NamedSharding-over-("batch",) program and uses every chip
    big = engine4.predict("prod", x[:2000])
    sharded_events = [e for e in get_recorder_events()
                      if e.name.startswith("serve:sharded:")]
    print(f"  2000-row request served SHARDED across "
          f"{len(devices)} devices -> output {big.shape} "
          f"({len(sharded_events)} sharded dispatch(es))")
    engine4.shutdown()

    _rollout_demo(x)
    _coldstart_demo(x)
    _costs_demo(x)
    _tiering_demo(x)


def _coldstart_demo(x):
    """Zero-cold-start finale: warm a model with the persistent
    executable cache on, then 'restart' (forget every in-memory
    executable), rebuild the engine from the manifest, and print the
    cold-compile vs warm-restart first-request split."""
    import tempfile

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.io.persistence import save_pca_model
    from spark_rapids_ml_tpu.obs import (
        clear_all_signature_caches,
        compile_stats,
        configure_executable_cache,
        get_executable_cache,
        reset_compile_log,
    )
    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine

    print("\n== zero cold start: persisted executables + warm-manifest "
          "restart ==")
    workdir = tempfile.mkdtemp(prefix="sparkml_coldstart_demo_")
    manifest = os.path.join(workdir, "manifest.json")
    model_path = os.path.join(workdir, "pca")
    configure_executable_cache(os.path.join(workdir, "aot_cache"))
    try:
        model = PCA().setK(8).fit(x)
        save_pca_model(model, model_path, overwrite=True)

        # deploy 1: the COLD arm — every ladder step pays an XLA
        # compile (the earlier demos warmed in-memory executables;
        # forget them so this deploy is a genuine cold start)
        clear_all_signature_caches()
        registry = ModelRegistry(manifest_path=manifest)
        registry.load("coldstart_pca", model_path)
        engine = ServeEngine(registry, max_batch_rows=256,
                             max_wait_ms=1.0)
        reset_compile_log()
        t0 = time.perf_counter()
        engine.warmup("coldstart_pca")
        engine.predict("coldstart_pca", x[:32])
        cold_ms = (time.perf_counter() - t0) * 1000.0
        cold_compiles = sum(s["compiles"]
                            for s in compile_stats().values())
        engine.shutdown()
        print(f"  cold deploy: first request after "
              f"{cold_ms:.0f} ms ({cold_compiles} XLA compiles; "
              f"cache stored {get_executable_cache().stats()['store']} "
              f"executables)")

        # 'restart': forget every in-memory executable, recover from
        # the manifest, replay the warm ladder through the disk cache
        clear_all_signature_caches()
        reset_compile_log()
        registry2 = ModelRegistry(manifest_path=manifest)
        t0 = time.perf_counter()
        engine2 = ServeEngine(registry2, max_batch_rows=256,
                              max_wait_ms=1.0)
        engine2.warm_from_manifest()
        engine2.predict("coldstart_pca", x[:32])
        warm_ms = (time.perf_counter() - t0) * 1000.0
        warm_compiles = sum(s["compiles"]
                            for s in compile_stats().values())
        engine2.shutdown()
        speedup = cold_ms / warm_ms if warm_ms > 0 else 0.0
        print(f"  warm restart: first request after {warm_ms:.0f} ms "
              f"({warm_compiles} fresh XLA compiles, "
              f"{get_executable_cache().stats()['hit']} cache hits) — "
              f"{speedup:.1f}x faster, restart is free")
        print("  -> which is what makes the autoscale controller "
              "(serve/autoscale.py) safe to be aggressive: replicas "
              "spawn warm")
    finally:
        configure_executable_cache(None)


def _rollout_demo(x):
    """Live rollout (serve/rollout.py): stream-fit a candidate while
    the incumbent serves, canary it on live alias traffic, inject a
    candidate-targeted regression, and watch the controller roll the
    alias back on its own."""
    import tempfile

    from spark_rapids_ml_tpu.serve import (
        RolloutController,
        StreamingTrainer,
        fault_plane,
    )

    print("\n== live rollout: streaming fit -> canary -> injected "
          "regression -> auto-rollback ==")
    model = PCA().setK(8).fit(x)
    registry = ModelRegistry()
    registry.register("rollout_pca", model, buckets=(32, 64))
    engine = ServeEngine(registry, max_batch_rows=64, max_wait_ms=1,
                         retries=0, breaker_failures=1000,
                         breaker_burn_threshold=0)
    rollout = RolloutController(
        engine, "rollout_pca", alias="live",
        fraction=0.4, shadow_tenant="canary_shadow",
        min_requests=6, eval_interval_s=0.05, regressed_hold_s=2.0)
    engine.attach_rollout(rollout)
    rollout.promote(1)
    print("  v1 promoted behind alias 'live' (warmed, then one pinned "
          "alias flip)")

    trainer = StreamingTrainer(
        registry, "rollout_pca", x.shape[1], 8, batches_per_version=4,
        artifact_dir=tempfile.mkdtemp(prefix="sparkml_rollout_demo_"),
        rollout=rollout)
    for i in range(4):
        trainer.feed(x[i * 128:(i + 1) * 128])
    print(f"  streaming trainer folded 4 live batches -> published "
          f"candidate v{rollout.candidate} "
          f"(artifact persisted, manifest-recoverable)")

    rollout.start_canary()
    print(f"  canary started: 40% of 'live' traffic -> v2, pinned to "
          f"tenant 'canary_shadow' (the fairness ledger audits it)")
    plane = fault_plane()
    plane.inject("rollout_pca", "raise", count=None,
                 version=rollout.canary_version)
    print("  injected: 100% backend errors targeted at v2 ONLY")

    served = {1: 0, 2: 0}
    errors = 0
    for i in range(60):
        if not rollout.canary_active:
            break
        try:
            engine.predict("live", x[i % 400:i % 400 + 8])
            served[1] += 1
        except Exception:
            errors += 1
            served[2] += 1
    decisions = [d for d in rollout.decisions
                 if d["action"] == "rollback"]
    print(f"  drove traffic: v1 answered {served[1]}, v2 failed "
          f"{errors} -> auto-rollback: {bool(decisions)}")
    if decisions:
        print(f"    reason: {decisions[0]['reason']}")
    print(f"  alias 'live' now serves "
          f"v{registry.resolve_entry('live').version}; "
          f"sparkml_serve_canary_regressed{{candidate=\"2\"}} raised -> "
          f"the serve_canary_regressed incident names the candidate")
    plane.clear()
    for i in range(10):
        engine.predict("live", x[i:i + 8])
    print("  post-rollback: 10/10 alias requests served by the "
          "incumbent (the armed fault targets only v2)")
    engine.shutdown()


def _costs_demo(x):
    """The closing number: the per-model cost attribution plane
    (obs/accounting.py). Two models share the engine — one hot, one
    idle after a brief burst — and the LIVE ``/debug/costs`` rollup is
    read back over the wire: accounted HBM residency by component,
    device-seconds reconciled against devmon at the same batch seam,
    and the ranked cold-model report a tiering controller would evict
    by."""
    import json
    import urllib.request

    from spark_rapids_ml_tpu.serve import start_serve_server

    print("\n== per-model cost attribution: GET /debug/costs ==")
    registry = ModelRegistry()
    registry.register("hot_embedder", PCA().setK(8).fit(x))
    registry.register("idle_embedder", PCA().setK(8).fit(x))
    engine = ServeEngine(registry, max_batch_rows=128, max_wait_ms=2)
    server = start_serve_server(engine)
    try:
        engine.warmup("hot_embedder")
        engine.warmup("idle_embedder")
        # one opening burst each, then only the hot model keeps serving
        for name in ("hot_embedder", "idle_embedder"):
            for i in range(3):
                engine.predict(name, x[i * 32:(i + 1) * 32])
        for i in range(60):
            engine.predict("hot_embedder", x[i * 16:i * 16 + 24])
        time.sleep(0.3)  # let the last completions land on both meters

        base = f"http://127.0.0.1:{server.server_address[1]}"
        doc = json.loads(urllib.request.urlopen(
            f"{base}/debug/costs", timeout=30).read())
        print(f"  live rollup from {base}/debug/costs:")
        for name, m in sorted(doc["models"].items()):
            hbm = m["hbm_bytes"]
            print(f"    {name:<14} hbm {m['hbm_total_bytes']:>6} B "
                  f"(weights {hbm['weights']}, reserve {hbm['reserve']}, "
                  f"executables {hbm['executables']})  "
                  f"device {m['device_seconds'] * 1000:7.1f} ms  "
                  f"rows {m['rows']:>5}  ewma {m['ewma_rps']:8.1f} r/s  "
                  f"last hit {m['last_hit_age_seconds']:.1f}s ago")
        rec = doc["reconcile"]
        print(f"  reconcile vs devmon (same seam, independent meter): "
              f"verdict={rec['verdict']}, worst drift "
              f"{rec['worst_drift_ratio']:.4f} "
              f"(tolerance {rec['tolerance']})")
        print("  cold-model report (coldest first — the eviction order "
              "a tiering controller reads):")
        for row in doc["cold_report"]:
            print(f"    {row['model']:<14} score {row['cold_score']:12.1f}"
                  f"  ({row['resident_bytes']} B resident, "
                  f"{row['ewma_rps']:.1f} r/s)")
    finally:
        server.shutdown()
        engine.shutdown()


def _tiering_demo(x):
    """The finale: model tiering (serve/tiering.py). Two models under
    a deliberately tight HBM budget — the idle one is driven COLD
    (drain, release its accounted bytes, keep its registry entry and
    warmed buckets), then the next request to it blocks in admission,
    reactivates through the compile caches with ZERO fresh XLA
    compiles, and is served. The tiering state table is printed at
    each step."""
    from spark_rapids_ml_tpu.obs.accounting import get_ledger
    from spark_rapids_ml_tpu.obs.xprof import (
        compile_stats,
        reset_compile_log,
    )
    from spark_rapids_ml_tpu.serve import TieringController

    def state_table(ctrl, header):
        snap = ctrl.snapshot()
        resident = {r["model"]: r["resident_bytes"]
                    for r in snap["cold_report"]}
        print(f"  {header} (budget {snap['hbm_budget_bytes']} B, "
              f"resident {snap['resident_bytes']} B):")
        for name, state in sorted(snap["states"].items()):
            pin = " [pinned]" if name in snap["pinned"] else ""
            print(f"    {name:<14} {state.upper():<12} "
                  f"{resident.get(name, 0):>6} B resident{pin}")

    print("\n== model tiering: hot/cold lifecycle under an HBM "
          "budget ==")
    registry = ModelRegistry()
    registry.register("head_model", PCA().setK(8).fit(x))
    registry.register("tail_model", PCA().setK(8).fit(x))
    engine = ServeEngine(registry, max_batch_rows=128, max_wait_ms=2)
    try:
        engine.warmup("head_model")
        engine.warmup("tail_model")
        engine.predict("tail_model", x[:32])
        time.sleep(0.05)
        for i in range(20):  # the head stays hot, the tail goes idle
            engine.predict("head_model", x[i * 16:i * 16 + 24])
        want = engine.predict("tail_model", x[:32])  # reference output

        ledger = get_ledger()
        total = sum(ledger.memory_bytes().values())
        # a budget one byte short of residency: the ledger's cold
        # report ranks tail_model coldest, so it pays
        ctrl = TieringController(
            engine, hbm_budget_bytes=total - 1, flap_floor_s=0.0,
            interval_s=0.25, per_model_autoscale=False, enabled=True,
            pins=("head_model",))
        engine.attach_tiering(ctrl)
        state_table(ctrl, "before the tick")
        actions = ctrl.evaluate_once()
        state_table(ctrl, "after eviction")
        evicted = [a["model"] for a in actions]
        print(f"  evicted {evicted}: bytes released, registry entry + "
              f"warmed buckets + on-disk executables KEPT "
              f"(registry still resolves: "
              f"{bool(registry.resolve_entry('tail_model'))})")

        reset_compile_log()
        t0 = time.perf_counter()
        got = engine.predict("tail_model", x[:32])  # the cold first hit
        first_hit_ms = (time.perf_counter() - t0) * 1000
        fresh = sum(s["compiles"] for s in compile_stats().values())
        bit_equal = bool(np.array_equal(want, got))
        state_table(ctrl, "after the cold first hit")
        print(f"  cold first hit: admission gated, reactivated, and "
              f"served in {first_hit_ms:.0f} ms with {fresh} fresh XLA "
              f"compiles (output bit-equal to pre-eviction: "
              f"{bit_equal})")
        events = [h["event"] for h in ctrl.lifecycle_history()]
        print(f"  lifecycle: {' -> '.join(events)}")
        print("  -> density scales with the registry; HBM scales with "
              "the working set (records/load_harness_density_r19.json "
              "proves it at 200 models)")
    finally:
        engine.shutdown()


def get_recorder_events():
    from spark_rapids_ml_tpu.obs import spans as spans_mod

    return spans_mod.get_recorder().events()


if __name__ == "__main__":
    main()
