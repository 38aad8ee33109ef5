#!/usr/bin/env python
"""Serving-engine bench: throughput, tail latency, batch occupancy.

Registers a fitted PCA model, warms its shape buckets, then drives a
fixed count of mixed-size predict requests through the engine from a
thread pool — the closed-loop analogue of real ragged traffic — and
emits ONE ``bench_common.emit_record`` JSON line so
``scripts/perf_sentinel.py`` can judge serving regressions against the
committed history from the next PR onward:

* ``rows_per_sec``            — end-to-end serving throughput;
* ``p50`` / ``p95`` / ``p99`` — request latency seconds (also under
  ``percentiles``, the sentinel's per-percentile judging shape), plus
  ``p99_ms`` (the same tail in milliseconds — the headline number the
  pipeline PR is judged on);
* ``p99_cold`` / ``p99_steady`` — the cold-vs-steady split: tail latency
  over the first ~10% of requests (first-touch compiles, cache warming,
  pipeline fill) vs the steady-state remainder — a warmup regression
  and a hot-path regression stop hiding behind one blended number;
* ``mean_batch_occupancy``    — real rows / bucket rows over the run
  (how well coalescing fills the padded shapes);
* ``pipeline_overlap_fraction`` — union device-busy time ÷ wall time
  (``sparkml_serve_device_busy_seconds_total`` over the run): how much
  of the bench wall-clock had at least one batch in flight. > 0
  whenever the pipelined batcher ran; the deeper companion
  ``pipeline_overlap2_fraction`` (>= 2 batches in flight) is the
  stage/compute overlap the PIPELINE_DEPTH=2 window buys;
* ``pipeline_depth``          — the engine's in-flight window, so a
  history line is attributable to its pipeline posture;
* ``recompile_count``         — distinct-signature compiles during the
  serve phase; steady state must stay at 0 (warmup owns them all);
* ``slo_fast_burn_rate``      — the worst fast-window (5 m) SLO burn rate
  at the end of the run (``obs.slo``; > 14.4 would page);
* ``slo_budget_remaining``    — the worst remaining error-budget fraction
  across the engine's SLOs. The sentinel judges this one
  HIGHER-is-better despite the fraction unit (see
  ``perf_sentinel.higher_is_better``).

Scenarios (``SPARKML_BENCH_SERVE_SCENARIO``):

* ``engine`` (default) — the single-model engine bench above, judged
  against the committed ``records/bench_serve_r09.json`` lineage;
* ``pipeline`` — staged-vs-FUSED whole-pipeline serving: one fitted
  scaler → PCA → logreg ``PipelineModel`` served twice through
  identical closed-loop traffic — once at ``pipeline_depth=1`` (the
  staged blocking per-stage loop, one host round trip per stage) and
  once through the fused one-XLA-program path — emitting
  ``metric="fused_p99_ms"`` (explicit lower-is-better) with
  ``staged_p99_ms`` and the speedup alongside;
* ``wire`` — JSON-vs-binary wire format over the REAL HTTP server: the
  same rows sent both ways, parse-phase latency read back from the
  ``sparkml_serve_parse_seconds{format}`` sketch ``serve.wire``'s
  decoders feed — emitting ``metric="wire_parse_ms_p99"`` (the binary
  parse tail, explicit lower-is-better) with ``json_parse_ms_p99`` and
  the parse speedup alongside;
* ``multidevice`` — the replicated serving tier's scaling proof: the
  same closed-loop engine bench run in SUBPROCESSES at forced host
  device counts 1 / 2 / 4 (``XLA_FLAGS=
  --xla_force_host_platform_device_count=N`` — device count is fixed at
  jax init, so each count needs its own process), emitting
  ``rows_per_sec`` per count and ``metric="serve_multidevice_scaling_
  efficiency"`` = (rows/sec at N ÷ rows/sec at 1) ÷ N (explicit
  higher-is-better). **CPU-CI honesty**: a single-core container
  cannot exhibit real FLOPS parallelism across virtual host devices,
  so the scenario models a fixed per-batch device service time
  (``SPARKML_BENCH_SERVE_DEVICE_MS``, default 60 — injected as a
  ``latency`` fault at every replica dispatch, a GIL-released sleep)
  and therefore judges the TIER: can placement + per-replica
  batchers/staging-pools keep N devices concurrently busy? On real
  multi-chip hardware set ``SPARKML_BENCH_SERVE_DEVICE_MS=0`` to
  measure true compute scaling. The modeled service time is stamped
  into the record so a baseline can never silently mix the two modes.

CPU-only harness: the subprocess scenarios are simulations on forced
host devices (``--xla_force_host_platform_device_count`` is honoured by
the CPU backend only), so their children are pinned to
``JAX_PLATFORMS=cpu`` — an inherited ``tpu`` would have every child
fight the parent for the one chip. Nothing they print is a device
number.

Knobs (env): SPARKML_BENCH_SERVE_REQUESTS (default 512),
SPARKML_BENCH_SERVE_FEATURES (64), SPARKML_BENCH_SERVE_K (16),
SPARKML_BENCH_SERVE_THREADS (8), SPARKML_BENCH_SERVE_MAX_ROWS (512),
plus the engine's SPARK_RAPIDS_ML_TPU_SERVE_{PIPELINE_DEPTH,PRECISION}.
"""

from __future__ import annotations

import concurrent.futures
import os
import sys
import time

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


def _closed_loop(predict, n_requests: int, n_threads: int):
    """Drive ``predict(i)`` from a thread pool; returns the per-request
    latency array and the wall time."""
    latencies = np.zeros(n_requests)

    def one(i: int) -> None:
        t0 = time.perf_counter()
        predict(i)
        latencies[i] = time.perf_counter() - t0

    t_run = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(n_threads) as pool:
        list(pool.map(one, range(n_requests)))
    return latencies, time.perf_counter() - t_run


def _fit_pipeline(rng, n_features: int, k: int):
    """One fitted scaler → PCA → binary-logreg PipelineModel plus its
    training matrix — the fused-serving specimen."""
    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models.logistic_regression import (
        LogisticRegression,
    )
    from spark_rapids_ml_tpu.models.pipeline import Pipeline
    from spark_rapids_ml_tpu.models.scaler import StandardScaler

    x = rng.normal(size=(4096, n_features))
    y = (x[:, 0] + 0.25 * x[:, 1] > 0).astype(float)
    frame = VectorFrame({"features": x, "label": list(y)})
    pipeline = Pipeline(stages=[
        StandardScaler().setWithMean(True).setOutputCol("scaled"),
        PCA().setK(k).setInputCol("scaled").setOutputCol("reduced"),
        LogisticRegression().setInputCol("reduced").setLabelCol("label"),
    ])
    return pipeline.fit(frame), x


def scenario_pipeline(device) -> int:
    """Staged-vs-fused whole-pipeline serving, closed loop, same
    traffic — the Flare-transplant headline number."""
    n_requests = _env_int("SPARKML_BENCH_SERVE_REQUESTS", 512)
    n_features = _env_int("SPARKML_BENCH_SERVE_FEATURES", 64)
    k = _env_int("SPARKML_BENCH_SERVE_K", 16)
    n_threads = _env_int("SPARKML_BENCH_SERVE_THREADS", 8)
    max_rows = _env_int("SPARKML_BENCH_SERVE_MAX_ROWS", 512)

    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine

    rng = np.random.default_rng(7)
    model, x = _fit_pipeline(rng, n_features, k)
    sizes = rng.integers(1, 257, size=n_requests).tolist()
    starts = [int(rng.integers(0, x.shape[0] - n)) for n in sizes]

    results = {}
    # depths explicit on BOTH arms: the fused arm must not inherit a
    # SPARK_RAPIDS_ML_TPU_SERVE_PIPELINE_DEPTH=1 kill switch from the
    # environment and silently measure the staged loop twice
    for mode, depth in (("staged", 1), ("fused", 2)):
        registry = ModelRegistry()
        registry.register("bench_pipeline", model)
        engine = ServeEngine(
            registry, max_batch_rows=max_rows, max_wait_ms=2.0,
            max_queue_depth=4 * n_requests, pipeline_depth=depth,
        )
        # depth=1 at native precision never builds the fused program —
        # the staged mode IS the blocking per-stage transform loop
        engine.warmup("bench_pipeline")
        latencies, wall = _closed_loop(
            lambda i: engine.predict(
                "bench_pipeline", x[starts[i]:starts[i] + sizes[i]]),
            n_requests, n_threads)
        engine.shutdown()
        results[mode] = {
            "p50": float(np.percentile(latencies, 50)),
            "p99": float(np.percentile(latencies, 99)),
            "wall": wall,
            "rows_per_sec": sum(sizes) / wall if wall > 0 else 0.0,
        }
    fused_p99_ms = results["fused"]["p99"] * 1000.0
    staged_p99_ms = results["staged"]["p99"] * 1000.0
    bench_common.emit_record({
        "bench": "serve_pipeline_fused",
        "metric": "fused_p99_ms",
        "value": fused_p99_ms,
        "unit": "ms (p99 fused whole-pipeline request latency)",
        "higher_is_better": False,
        "platform": device.platform,
        "device_kind": str(device.device_kind),
        "requests": n_requests,
        "threads": n_threads,
        "stages": 3,
        "fused_p99_ms": fused_p99_ms,
        "staged_p99_ms": staged_p99_ms,
        "fused_p50_ms": results["fused"]["p50"] * 1000.0,
        "staged_p50_ms": results["staged"]["p50"] * 1000.0,
        "fused_rows_per_sec": results["fused"]["rows_per_sec"],
        "staged_rows_per_sec": results["staged"]["rows_per_sec"],
        "fused_speedup_p99": (staged_p99_ms / fused_p99_ms
                              if fused_p99_ms > 0 else 0.0),
    }, include_metrics=False)
    return 0


def scenario_wire(device) -> int:
    """JSON-vs-binary wire parse over the real HTTP server: identical
    rows both ways, verdict read from the decoders' own parse-latency
    sketch (the measured, not asserted, protocol cost)."""
    import http.client
    import json

    # More observations than the engine bench: the binary parse is tens
    # of µs, so its p99 estimate needs a deep sample to sit above the
    # OS-scheduler spike noise instead of IN it.
    n_requests = _env_int("SPARKML_BENCH_SERVE_REQUESTS", 1024)
    n_features = _env_int("SPARKML_BENCH_SERVE_FEATURES", 64)
    k = _env_int("SPARKML_BENCH_SERVE_K", 16)
    max_rows = _env_int("SPARKML_BENCH_SERVE_MAX_ROWS", 512)
    rows_per_request = _env_int("SPARKML_BENCH_SERVE_WIRE_ROWS", 256)

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine
    from spark_rapids_ml_tpu.serve import wire
    from spark_rapids_ml_tpu.serve.server import start_serve_server

    rng = np.random.default_rng(7)
    x = rng.normal(size=(4096, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("bench_pca", model)
    engine = ServeEngine(registry, max_batch_rows=max_rows,
                         max_wait_ms=2.0,
                         max_queue_depth=4 * n_requests)
    engine.warmup("bench_pca")
    server = start_serve_server(engine)
    port = server.server_address[1]

    starts = [int(rng.integers(0, x.shape[0] - rows_per_request))
              for _ in range(n_requests)]
    e2e = {}
    try:
        for fmt in ("json", "binary"):
            conn = http.client.HTTPConnection("127.0.0.1", port)
            lat = np.zeros(n_requests)
            for i, start in enumerate(starts):
                batch = x[start:start + rows_per_request]
                if fmt == "json":
                    body = json.dumps({"model": "bench_pca",
                                       "rows": batch.tolist()})
                    headers = {"Content-Type": "application/json"}
                else:
                    body = wire.encode_request("bench_pca", batch,
                                               dtype=np.float64)
                    headers = {"Content-Type": wire.BINARY_CONTENT_TYPE}
                t0 = time.perf_counter()
                conn.request("POST", "/predict", body, headers)
                resp = conn.getresponse()
                resp.read()
                lat[i] = time.perf_counter() - t0
                if resp.status != 200:
                    raise RuntimeError(
                        f"{fmt} request {i} failed: {resp.status}")
            conn.close()
            e2e[fmt] = {"p50": float(np.percentile(lat, 50)),
                        "p99": float(np.percentile(lat, 99))}
    finally:
        server.shutdown()
        engine.shutdown()
    json_q = wire.parse_quantiles("json")
    bin_q = wire.parse_quantiles("binary")
    json_p99_ms = (json_q.get("p99") or 0.0) * 1000.0
    bin_p99_ms = (bin_q.get("p99") or 0.0) * 1000.0
    bench_common.emit_record({
        "bench": "serve_wire_format",
        "metric": "wire_parse_ms_p99",
        "value": bin_p99_ms,
        "unit": "ms (p99 binary request-body parse latency)",
        "higher_is_better": False,
        "platform": device.platform,
        "device_kind": str(device.device_kind),
        "requests": n_requests,
        "rows_per_request": rows_per_request,
        "wire_parse_ms_p99": bin_p99_ms,
        "json_parse_ms_p99": json_p99_ms,
        "wire_parse_ms_p50": (bin_q.get("p50") or 0.0) * 1000.0,
        "json_parse_ms_p50": (json_q.get("p50") or 0.0) * 1000.0,
        "parse_speedup_p99": (json_p99_ms / bin_p99_ms
                              if bin_p99_ms > 0 else 0.0),
        "json_e2e_p99_ms": e2e["json"]["p99"] * 1000.0,
        "binary_e2e_p99_ms": e2e["binary"]["p99"] * 1000.0,
    }, include_metrics=False)
    return 0


CHILD_RESULT_PREFIX = "MULTIDEVICE_CHILD_RESULT "
COLDSTART_CHILD_PREFIX = "COLDSTART_CHILD_RESULT "


def scenario_coalesce() -> int:
    """Load-aware coalescing concentration, the PR 13 ROADMAP item: at
    4 replicas, spreading SMALL requests least-loaded across every
    queue thinned batches to ~1.6 requests/batch (vs ~4 at 1 replica).
    This scenario runs the SAME small-request closed loop twice in
    4-device subprocesses — concentration ON (the new default: the
    small-request tier routes to the lowest-index lightly-loaded
    replica, spilling as depth grows) vs OFF (pure least-loaded) — and
    emits ``metric="serve_coalesce_density_ratio"`` = requests/batch ON
    ÷ OFF (explicit higher-is-better). Gate (rc=1): the ratio must
    clear ``SPARKML_BENCH_COALESCE_MIN`` (default 1.3)."""
    import subprocess

    min_ratio = float(os.environ.get("SPARKML_BENCH_COALESCE_MIN",
                                     "1.3"))
    results = {}
    for mode, flag in (("concentrated", "1"), ("spread", "0")):
        env = dict(os.environ)
        env["SPARKML_BENCH_SERVE_SCENARIO"] = "_multidevice_child"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = bench_common.force_device_count_flags(4)
        env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
        env["SPARK_RAPIDS_ML_TPU_SERVE_CONCENTRATE"] = flag
        # the small-request tier under LIGHT load: quarter-bucket
        # requests from few threads — the regime the PR 13 bench showed
        # thinning batches across N replica queues
        env.setdefault("SPARKML_BENCH_SERVE_MD_ROWS", "64")
        env.setdefault("SPARKML_BENCH_SERVE_MD_REQUESTS", "192")
        env.setdefault("SPARKML_BENCH_SERVE_THREADS", "4")
        env.setdefault("SPARKML_BENCH_SERVE_DEVICE_MS", "15")
        bench_common.log(f"bench_serve coalesce: {mode} child at "
                         f"4 device(s)")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        result = bench_common.prefixed_result(proc.stdout,
                                              CHILD_RESULT_PREFIX)
        if proc.returncode != 0 or result is None:
            bench_common.log(
                f"coalesce {mode} child FAILED "
                f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
            return 1
        results[mode] = result
    on = results["concentrated"]
    off = results["spread"]
    ratio = (on["requests_per_batch"] / off["requests_per_batch"]
             if off["requests_per_batch"] else 0.0)
    bench_common.emit_record({
        "bench": "serve_coalesce",
        "metric": "serve_coalesce_density_ratio",
        "value": ratio,
        "unit": ("requests/batch with small-request concentration ON "
                 "over OFF at 4 replicas under light load"),
        "higher_is_better": True,
        "platform": on["platform"],
        "device_kind": on["device_kind"],
        "requests": on["requests"],
        "rows_per_request": on["rows_per_request"],
        "threads": on["threads"],
        "density_concentrated": on["requests_per_batch"],
        "density_spread": off["requests_per_batch"],
        "batches_concentrated": on["batches"],
        "batches_spread": off["batches"],
        "rows_per_sec_concentrated": on["rows_per_sec"],
        "rows_per_sec_spread": off["rows_per_sec"],
        "p99_ms_concentrated": on["p99_ms"],
        "p99_ms_spread": off["p99_ms"],
        "replica_split_concentrated": on["replica_split"],
        "replica_split_spread": off["replica_split"],
    }, include_metrics=False)
    bench_common.log(
        f"bench_serve coalesce: {on['requests_per_batch']:.2f} req/"
        f"batch concentrated vs {off['requests_per_batch']:.2f} spread "
        f"({ratio:.2f}x)")
    if ratio < min_ratio:
        bench_common.log(
            f"bench_serve coalesce FAIL: density ratio {ratio:.2f} < "
            f"{min_ratio}")
        return 1
    return 0


def scenario_coldstart() -> int:
    """The zero-cold-start proof: warm-restart vs cold-compile, each in
    its own subprocess (a REAL process restart — in-memory jit caches
    cannot leak across).

    A prepare child fits + saves a PCA model, registers it in a
    manifest-backed registry, and warms the full bucket ladder with the
    persistent executable cache enabled (populating both the warm
    manifest and the cache). Then two restart children each recover the
    registry from the manifest, rebuild the engine, replay the warm
    manifest (``engine.warm_from_manifest``) and serve a first request:

    * the **cold** arm runs with the cache DISABLED — every ladder step
      pays a fresh XLA lower+compile (what every restart cost before
      this tier);
    * the **warm** arm runs with the cache on — every ladder step loads
      its executable from disk, and the child asserts ZERO fresh
      compiles via ``obs.xprof.signature_count`` accounting.

    Emits ``metric="serve_cold_start_ms"`` (the warm arm, explicit
    lower-is-better) with the cold arm and the speedup alongside.
    Gates (rc=1): the warm arm must show zero fresh compiles and be at
    least ``SPARKML_BENCH_COLDSTART_MIN_RATIO`` (default 10) times
    faster than the cold arm."""
    import json
    import subprocess
    import tempfile

    min_ratio = float(os.environ.get(
        "SPARKML_BENCH_COLDSTART_MIN_RATIO", "10"))
    workdir = tempfile.mkdtemp(prefix="sparkml_coldstart_")
    cache_dir = os.path.join(workdir, "aot_cache")
    manifest = os.path.join(workdir, "manifest.json")

    def _child(mode: str, cached: bool):
        env = dict(os.environ)
        env["SPARKML_BENCH_SERVE_SCENARIO"] = "_coldstart_child"
        env["SPARKML_BENCH_COLDSTART_MODE"] = mode
        env["SPARKML_BENCH_COLDSTART_DIR"] = workdir
        env["SPARK_RAPIDS_ML_TPU_SERVE_MANIFEST"] = manifest
        env["JAX_PLATFORMS"] = "cpu"
        # a production-shaped bucket ladder (the finer steps the PR 9+
        # pipeline tier actually serves with) — the restart tax scales
        # with ladder size, which is exactly what the cache amortizes
        env.setdefault(
            "SPARK_RAPIDS_ML_TPU_SERVE_BUCKETS",
            "8,16,24,32,48,64,96,128,192,256,384,512,768,1024")
        if cached:
            env["SPARK_RAPIDS_ML_TPU_SERVE_CACHE_DIR"] = cache_dir
        else:
            env.pop("SPARK_RAPIDS_ML_TPU_SERVE_CACHE_DIR", None)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=600,
        )
        result = bench_common.prefixed_result(proc.stdout,
                                              COLDSTART_CHILD_PREFIX)
        if proc.returncode != 0 or result is None:
            bench_common.log(
                f"coldstart {mode} child FAILED "
                f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
            return None
        return result

    bench_common.log("bench_serve coldstart: prepare (fit + warm + "
                     "populate cache)")
    prepared = _child("prepare", cached=True)
    if prepared is None:
        return 1
    bench_common.log("bench_serve coldstart: cold-compile restart arm")
    cold = _child("restart", cached=False)
    if cold is None:
        return 1
    bench_common.log("bench_serve coldstart: warm-restart arm")
    warm = _child("restart", cached=True)
    if warm is None:
        return 1
    speedup = (cold["cold_start_ms"] / warm["cold_start_ms"]
               if warm["cold_start_ms"] > 0 else 0.0)
    record = {
        "bench": "serve_coldstart",
        "metric": "serve_cold_start_ms",
        "value": warm["cold_start_ms"],
        "unit": ("ms from registry recovery to first served request "
                 "on a warm restart (persisted executable cache)"),
        "higher_is_better": False,
        "platform": warm["platform"],
        "device_kind": warm["device_kind"],
        "serve_cold_start_ms": warm["cold_start_ms"],
        "cold_compile_ms": cold["cold_start_ms"],
        "coldstart_speedup": speedup,
        "warm_fresh_compiles": warm["fresh_compiles"],
        "cold_fresh_compiles": cold["fresh_compiles"],
        "warm_first_request_ms": warm["first_request_ms"],
        "cold_first_request_ms": cold["first_request_ms"],
        "manifest_recovery_ms": warm.get("recovery_ms"),
        "warmed_buckets": warm["warmed_buckets"],
        "cache_entries": warm.get("cache_entries"),
        "cache_hits": warm.get("cache_hits"),
        "features": warm["features"],
        "k": warm["k"],
    }
    bench_common.emit_record(record, include_metrics=False)
    bench_common.log(
        f"bench_serve coldstart: warm {warm['cold_start_ms']:.0f} ms vs "
        f"cold {cold['cold_start_ms']:.0f} ms ({speedup:.1f}x), warm "
        f"fresh compiles {warm['fresh_compiles']}")
    failures = []
    if warm["fresh_compiles"] != 0:
        failures.append(
            f"warm restart paid {warm['fresh_compiles']} fresh XLA "
            "compile(s) — the cache missed")
    if speedup < min_ratio:
        failures.append(
            f"warm restart only {speedup:.1f}x faster than cold "
            f"compile < {min_ratio}x")
    if failures:
        bench_common.log("bench_serve coldstart FAIL: "
                         + "; ".join(failures))
        return 1
    return 0


def scenario_coldstart_child(device) -> int:
    """One cold-start arm (own process — see ``scenario_coldstart``).

    ``prepare`` fits + saves + registers + warms (populating the warm
    manifest and, when configured, the executable cache). ``restart``
    measures the restart path: manifest recovery → engine →
    ``warm_from_manifest`` → first request, reporting the total ms and
    the number of fresh XLA compiles the restart paid."""
    import json

    import jax.numpy as jnp

    from spark_rapids_ml_tpu.obs import compile_stats
    from spark_rapids_ml_tpu.obs.aotcache import get_executable_cache
    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine

    mode = os.environ.get("SPARKML_BENCH_COLDSTART_MODE", "prepare")
    workdir = os.environ["SPARKML_BENCH_COLDSTART_DIR"]
    # a REALISTIC deploy shape: the fused scaler → PCA → logreg pipeline
    # (one fused XLA program per bucket plus the three per-stage sync
    # kernels) — the ladder whose recompile cost is the actual restart
    # tax this tier removes
    n_features = _env_int("SPARKML_BENCH_SERVE_FEATURES", 512)
    k = _env_int("SPARKML_BENCH_SERVE_K", 128)
    max_rows = _env_int("SPARKML_BENCH_SERVE_MAX_ROWS", 1024)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2048, n_features))
    model_path = os.path.join(workdir, "coldstart_pipeline")

    def _fresh_compiles() -> int:
        return sum(s["compiles"] for s in compile_stats().values())

    if mode == "prepare":
        from spark_rapids_ml_tpu import PCA
        from spark_rapids_ml_tpu.data.frame import VectorFrame
        from spark_rapids_ml_tpu.models.feature_scalers import (
            MaxAbsScaler,
            Normalizer,
        )
        from spark_rapids_ml_tpu.models.logistic_regression import (
            LogisticRegression,
        )
        from spark_rapids_ml_tpu.models.pipeline import Pipeline
        from spark_rapids_ml_tpu.models.scaler import StandardScaler

        # a five-stage fused chain: the deeper the pipeline, the bigger
        # the per-bucket XLA program — exactly the restart tax profile
        # of a production deploy
        y = (x[:, 0] + 0.25 * x[:, 1] > 0).astype(float)
        frame = VectorFrame({"features": x, "label": list(y)})
        model = Pipeline(stages=[
            StandardScaler().setWithMean(True).setOutputCol("s1"),
            MaxAbsScaler().setInputCol("s1").setOutputCol("s2"),
            Normalizer().setInputCol("s2").setOutputCol("s3"),
            PCA().setK(k).setInputCol("s3").setOutputCol("reduced"),
            LogisticRegression().setInputCol("reduced")
                                .setLabelCol("label"),
        ]).fit(frame)
        model.save(model_path, overwrite=True)
        registry = ModelRegistry()  # manifest via env
        registry.load("coldstart_pipeline", model_path)
        engine = ServeEngine(registry, max_batch_rows=max_rows,
                             max_wait_ms=2.0)
        report = engine.warmup("coldstart_pipeline")
        engine.predict("coldstart_pipeline", x[:32])
        engine.shutdown()
        result = {
            "mode": mode,
            "platform": device.platform,
            "device_kind": str(device.device_kind),
            "warmed_buckets": sorted(report["buckets"]),
            "features": n_features,
            "k": k,
        }
    else:
        # Both arms pay jax backend init, eager-dispatch warm-in, and
        # the manifest's model load identically and OUTSIDE the
        # measured window: serve_cold_start_ms is the COMPILE tax this
        # tier removes — engine build → warm-manifest replay → first
        # served request. (Manifest model recovery is PR 6's measured
        # cost; the eager pre-touch mirrors any process that did
        # anything at all with jax before serving.)
        jnp.asarray(np.zeros((4, 4))).astype(jnp.float32)
        (jnp.zeros((4, 4), jnp.float32)
         @ jnp.zeros((4, 4), jnp.float32)).block_until_ready()
        t_rec = time.perf_counter()
        registry = ModelRegistry()  # manifest via env → recovery
        recovery_ms = (time.perf_counter() - t_rec) * 1000.0
        t0 = time.perf_counter()
        engine = ServeEngine(registry, max_batch_rows=max_rows,
                             max_wait_ms=2.0)
        warm_report = engine.warm_from_manifest()
        t_warm = time.perf_counter()
        engine.predict("coldstart_pipeline", x[:32])
        t_first = time.perf_counter()
        compiles = _fresh_compiles()
        cache = get_executable_cache()
        cache_stats = cache.stats() if cache is not None else {}
        engine.shutdown()
        if warm_report["failed"] or not warm_report["warmed"]:
            sys.stderr.write(
                f"warm_from_manifest failed: {warm_report}\n")
            return 1
        result = {
            "mode": mode,
            "platform": device.platform,
            "device_kind": str(device.device_kind),
            "cold_start_ms": (t_first - t0) * 1000.0,
            "warmup_ms": (t_warm - t0) * 1000.0,
            "first_request_ms": (t_first - t_warm) * 1000.0,
            "recovery_ms": recovery_ms,
            "fresh_compiles": compiles,
            "warmed_buckets": sorted(
                int(b) for _n, _v, bk in registry.warm_entries()
                for b in bk),
            "cache_entries": cache_stats.get("entries"),
            "cache_hits": cache_stats.get("hit"),
            "features": n_features,
            "k": k,
        }
    sys.stdout.write(COLDSTART_CHILD_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def scenario_multidevice() -> int:
    """Parent leg: run the closed-loop child at device counts 1/2/4 in
    subprocesses, aggregate into ONE sentinel-judged record. Runs
    before any jax import — device count is fixed at jax init, so the
    parent must never initialize a backend itself."""
    import subprocess

    counts = [int(v) for v in os.environ.get(
        "SPARKML_BENCH_SERVE_DEVICES", "1,2,4").split(",") if v.strip()]
    device_ms = float(os.environ.get("SPARKML_BENCH_SERVE_DEVICE_MS",
                                     "60"))
    results = {}
    for n in counts:
        env = dict(os.environ)
        env["SPARKML_BENCH_SERVE_SCENARIO"] = "_multidevice_child"
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = bench_common.force_device_count_flags(n)
        # the child replicates onto every device it sees
        env.pop("SPARK_RAPIDS_ML_TPU_SERVE_REPLICAS", None)
        bench_common.log(f"bench_serve multidevice: child at "
                         f"{n} device(s)")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=900,
        )
        result = bench_common.prefixed_result(proc.stdout,
                                              CHILD_RESULT_PREFIX)
        if proc.returncode != 0 or result is None:
            bench_common.log(
                f"multidevice child at {n} device(s) FAILED "
                f"(rc={proc.returncode}): {proc.stderr[-2000:]}")
            return 1
        results[n] = result
    base_count, top = counts[0], counts[-1]
    base = results[base_count]["rows_per_sec"]
    speedups = {n: (results[n]["rows_per_sec"] / base if base else 0.0)
                for n in counts}
    # efficiency relative to the MEASURED base count — with the default
    # counts "1,2,4" this is the classic (rps_N / rps_1) / N, but an
    # operator benching only 2,4 gets the honest 2→4 efficiency instead
    # of a silently mislabeled number
    efficiency = (speedups[top] / (top / base_count)
                  if top > base_count else 0.0)
    record = {
        "bench": "serve_multidevice",
        "metric": "serve_multidevice_scaling_efficiency",
        "value": efficiency,
        "unit": (f"scaling efficiency: (rows/sec at {top} devices / "
                 f"rows/sec at {base_count}) / ({top}/{base_count})"),
        "higher_is_better": True,
        "platform": results[top]["platform"],
        "device_kind": results[top]["device_kind"],
        "device_counts": counts,
        "modeled_device_ms": device_ms,
        "requests": results[top]["requests"],
        "rows_per_request": results[top]["rows_per_request"],
        "threads": results[top]["threads"],
        "scaling_efficiency": efficiency,
        "speedup_at_top": speedups[top],
    }
    for n in counts:
        record[f"rows_per_sec_{n}"] = results[n]["rows_per_sec"]
        record[f"p99_ms_{n}"] = results[n]["p99_ms"]
        record[f"replica_split_{n}"] = results[n]["replica_split"]
    bench_common.emit_record(record, include_metrics=False)
    bench_common.log(
        "bench_serve multidevice: " + ", ".join(
            f"{n}dev {results[n]['rows_per_sec']:,.0f} rows/s"
            for n in counts)
        + f" -> speedup {speedups[top]:.2f}x at {top} devices "
          f"(efficiency {efficiency:.2f})")
    return 0


def scenario_multidevice_child(device) -> int:
    """One device count's closed-loop measurement (run in its own
    process — see ``scenario_multidevice``). Emits a machine-readable
    result line instead of a bench record; the parent aggregates."""
    import json

    n_requests = _env_int("SPARKML_BENCH_SERVE_MD_REQUESTS", 128)
    n_features = _env_int("SPARKML_BENCH_SERVE_FEATURES", 32)
    k = _env_int("SPARKML_BENCH_SERVE_K", 8)
    n_threads = _env_int("SPARKML_BENCH_SERVE_THREADS", 16)
    max_rows = _env_int("SPARKML_BENCH_SERVE_MAX_ROWS", 256)
    # full-bucket requests: one request = one batch = one modeled
    # device dispatch, so the measured scaling is the TIER's dispatch
    # concurrency, not a coalescing-density artifact (spreading small
    # requests across N queues thins batches — a real trade-off the
    # engine scenario covers; this scenario isolates the replica win)
    rows_per_request = _env_int("SPARKML_BENCH_SERVE_MD_ROWS", 256)
    device_ms = float(os.environ.get("SPARKML_BENCH_SERVE_DEVICE_MS",
                                     "60"))

    import jax

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.obs import get_registry
    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine
    from spark_rapids_ml_tpu.serve.faults import fault_plane

    n_devices = len(jax.devices())
    rng = np.random.default_rng(7)
    x = rng.normal(size=(4096, n_features))
    model = PCA().setK(k).fit(x)
    registry = ModelRegistry()
    registry.register("bench_md_pca", model)
    engine = ServeEngine(
        registry, max_batch_rows=max_rows, max_wait_ms=2.0,
        max_queue_depth=4 * n_requests,
    )
    engine.warmup("bench_md_pca")
    if device_ms > 0:
        # the modeled per-batch device service time: a latency fault at
        # EVERY replica dispatch (GIL-released sleep) — see the module
        # docstring's CPU-CI honesty note
        fault_plane().inject("bench_md_pca", "latency", count=None,
                             seconds=device_ms / 1000.0)
    starts = [int(rng.integers(0, x.shape[0] - rows_per_request))
              for _ in range(n_requests)]
    latencies, wall = _closed_loop(
        lambda i: engine.predict(
            "bench_md_pca",
            x[starts[i]:starts[i] + rows_per_request]),
        n_requests, n_threads)
    snap = get_registry().snapshot().get(
        "sparkml_serve_replica_batches_total", {"samples": []})
    split = {s["labels"]["device"]: s["value"]
             for s in snap["samples"] if s["value"] > 0}

    def _counter_total(name: str) -> float:
        doc = get_registry().snapshot().get(name, {"samples": []})
        return sum(s["value"] for s in doc["samples"])

    batches = _counter_total("sparkml_serve_batches_total")
    engine.shutdown()
    total_rows = n_requests * rows_per_request
    result = {
        "devices": n_devices,
        "platform": device.platform,
        "device_kind": str(device.device_kind),
        "requests": n_requests,
        "rows_per_request": rows_per_request,
        "threads": n_threads,
        "rows_per_sec": total_rows / wall if wall > 0 else 0.0,
        "p99_ms": float(np.percentile(latencies, 99)) * 1000.0,
        "replica_split": split,
        "batches": int(batches),
        "requests_per_batch": (n_requests / batches if batches else 0.0),
        "concentrate": os.environ.get(
            "SPARK_RAPIDS_ML_TPU_SERVE_CONCENTRATE", "1"),
    }
    sys.stdout.write(CHILD_RESULT_PREFIX + json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


def main() -> int:
    n_requests = _env_int("SPARKML_BENCH_SERVE_REQUESTS", 512)
    n_features = _env_int("SPARKML_BENCH_SERVE_FEATURES", 64)
    k = _env_int("SPARKML_BENCH_SERVE_K", 16)
    n_threads = _env_int("SPARKML_BENCH_SERVE_THREADS", 8)
    max_rows = _env_int("SPARKML_BENCH_SERVE_MAX_ROWS", 512)
    scenario = os.environ.get(
        "SPARKML_BENCH_SERVE_SCENARIO", "engine").strip().lower()

    if scenario == "multidevice":
        # MUST dispatch before the jax import below: the parent spawns
        # per-device-count children and never initializes a backend
        return scenario_multidevice()
    if scenario == "coldstart":
        # same rule: the parent only orchestrates restart children
        return scenario_coldstart()
    if scenario == "coalesce":
        return scenario_coalesce()

    import jax

    if scenario != "_coldstart_child":
        # (the cold-start scenario times the repo's own executable cache,
        # obs/aotcache.py, against a compile from nothing)
        from spark_rapids_ml_tpu.utils.platform import (
            configure_compile_cache,
        )

        configure_compile_cache()

    if scenario == "pipeline":
        return scenario_pipeline(jax.devices()[0])
    if scenario == "wire":
        return scenario_wire(jax.devices()[0])
    if scenario == "_multidevice_child":
        return scenario_multidevice_child(jax.devices()[0])
    if scenario == "_coldstart_child":
        return scenario_coldstart_child(jax.devices()[0])

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.obs import compile_stats, get_registry
    from spark_rapids_ml_tpu.serve import ModelRegistry, ServeEngine

    device = jax.devices()[0]
    rng = np.random.default_rng(7)
    x = rng.normal(size=(4096, n_features))
    model = PCA().setK(k).fit(x)

    registry = ModelRegistry()
    registry.register("bench_pca", model)
    engine = ServeEngine(
        registry, max_batch_rows=max_rows, max_wait_ms=2.0,
        max_queue_depth=4 * n_requests,
    )
    # engine.warmup also precompiles the pipeline's precision x bucket
    # ladder, so the cold split below measures cache/queue warming, not
    # first-request XLA compiles.
    engine.warmup("bench_pca")
    compiles_before = sum(
        s["compiles"] for s in compile_stats().values()
    )

    # Mixed-size closed-loop traffic: 1..256-row requests from N threads.
    # Sizes AND offsets precomputed — numpy Generators are not thread-safe,
    # and the seed must reproduce exactly for sentinel comparisons.
    sizes = rng.integers(1, 257, size=n_requests).tolist()
    starts = [int(rng.integers(0, x.shape[0] - n)) for n in sizes]
    latencies = np.zeros(n_requests)
    total_rows = int(sum(sizes))

    def one(i: int) -> None:
        n, start = sizes[i], starts[i]
        t0 = time.perf_counter()
        engine.predict("bench_pca", x[start:start + n])
        latencies[i] = time.perf_counter() - t0

    t_run = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(n_threads) as pool:
        list(pool.map(one, range(n_requests)))
    wall = time.perf_counter() - t_run
    # The engine's SloSet saw every request; read the verdict before
    # shutdown so the record carries the run's SLO posture.
    slos = list(engine.slo)
    slo_fast_burn = max(
        (s.burn_rate(300.0) for s in slos), default=0.0)
    slo_budget_remaining = min(
        (s.budget_remaining() for s in slos), default=1.0)
    engine.shutdown()

    compiles_after = sum(
        s["compiles"] for s in compile_stats().values()
    )

    def _counter(name: str) -> float:
        snap = get_registry().snapshot().get(name, {"samples": []})
        return sum(s["value"] for s in snap["samples"])

    batch_rows = _counter("sparkml_serve_batch_rows_total")
    bucket_rows = _counter("sparkml_serve_bucket_rows_total")
    busy_s = _counter("sparkml_serve_device_busy_seconds_total")
    overlap2_s = _counter("sparkml_serve_pipeline_overlap_seconds_total")
    p50, p95, p99 = (float(np.percentile(latencies, q))
                     for q in (50, 95, 99))
    # Cold-vs-steady split: the first ~10% of requests (pool.map submits
    # roughly in index order) carry first-touch costs — pipeline fill,
    # allocator/cache warming — the steady tail should not pay.
    n_cold = max(min(32, n_requests), n_requests // 10)
    p99_cold = float(np.percentile(latencies[:n_cold], 99))
    p99_steady = (float(np.percentile(latencies[n_cold:], 99))
                  if n_requests > n_cold else p99_cold)
    bench_common.emit_record({
        "bench": "serve_engine",
        # metric/value/unit make the record sentinel-judgeable as a
        # scalar (p99 seconds, lower-is-better via the "second" unit
        # heuristic) on top of the per-percentile judging that
        # `percentiles` triggers — without "metric" the sentinel could
        # not judge serve records at all.
        "metric": "serve_engine_latency",
        "value": float(np.percentile(latencies, 99)),
        "unit": "seconds (p99 request latency)",
        "platform": device.platform,
        "device_kind": str(device.device_kind),
        "requests": n_requests,
        "threads": n_threads,
        "rows": total_rows,
        "seconds": wall,
        "rows_per_sec": total_rows / wall if wall > 0 else 0.0,
        "p50": p50,
        "p95": p95,
        "p99": p99,
        "p99_ms": p99 * 1000.0,
        "p99_cold": p99_cold,
        "p99_steady": p99_steady,
        "percentiles": {"p50": p50, "p95": p95, "p99": p99},
        "mean_batch_occupancy": (
            batch_rows / bucket_rows if bucket_rows else 0.0
        ),
        "pipeline_overlap_fraction": busy_s / wall if wall > 0 else 0.0,
        "pipeline_overlap2_fraction": (
            overlap2_s / wall if wall > 0 else 0.0
        ),
        "pipeline_depth": engine.pipeline_depth,
        "precision": engine.precision,
        "recompile_count": int(compiles_after - compiles_before),
        "slo_fast_burn_rate": slo_fast_burn,
        "slo_budget_remaining": slo_budget_remaining,
        "batches": int(_counter("sparkml_serve_batches_total")),
        "deadline_expired": int(
            _counter("sparkml_serve_deadline_expired_total")
        ),
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
