"""Stdlib HTTP front end: predict + health + metrics + ops surface.

A thin JSON shim over ``ServeEngine`` so the whole serving stack is
drivable end-to-end (curl, load generators, k8s probes) without adding a
web framework to the container:

* ``POST /predict`` — JSON body ``{"model": "name[@version]",
  "rows": [[...], ...], "deadline_ms": 250, "tenant": "team-a",
  "priority": "interactive|batch"}`` (tenant/priority also accepted as
  ``X-Tenant`` / ``X-Priority`` headers; HEADERS win — the pre-parse
  fast-shed path can only see headers, so they must be authoritative;
  body fields serve header-less clients) → ``{"model",
  "version", "outputs": [...], "trace_id", "degraded", "retries"}``.
  **Binary columnar bodies** (``Content-Type:
  application/x-sparkml-columnar`` — ``serve.wire``: 24-byte header +
  contiguous row-major payload) skip the JSON parse entirely; the
  response mirrors the request format (or follows an explicit
  ``Accept``), with version/degraded/retries carried as ``X-Model-*``
  headers. ALL body decoding — both formats — routes through
  ``serve.wire`` decoders that record the parse-phase latency
  (``sparkml_serve_parse_seconds{format}``; rule 11 of
  ``scripts/check_instrumentation.py`` rejects bare ``json.loads`` on
  request bodies here). A malformed binary frame (bad magic, wrong
  version, unknown dtype, truncated/mismatched payload) replies
  400/415 with the distinct ``error="bad_wire"`` label; the full body
  was already read, so keep-alive never desyncs. Tenant/priority stay
  header-borne for binary traffic, so the pre-parse fast shed fires on
  it exactly as on JSON;
  admission rejection maps to **429**, an adaptive load-shed
  (``ShedLoad`` — the overload controller's verdict, distinct from a
  full queue) to **503** with ``"shed": true``, a shed deadline to
  **504**, an unknown model to **404**, malformed input to **400**, and
  the fault-tolerance outcomes to **503**: an open breaker with no CPU
  fallback (``BreakerOpen``) and a dead batcher worker
  (``WorkerCrashed``) are both retryable service states, not client
  errors. Every 429/503/504 overload rejection carries a
  ``Retry-After`` header derived from the live queue-wait estimate. A
  request served by the degraded CPU fallback still returns **200**
  with ``"degraded": true``. An inbound W3C ``traceparent`` header
  continues the caller's trace (Dapper-style propagation via
  ``obs.tracectx``); every response carries a ``traceparent`` back, and
  every error path replies with an explicit ``Content-Length``;
* ``GET /healthz`` — engine liveness + registered models + queue depth;
  the ``status`` field is overload-aware (``ok`` / ``shedding`` /
  ``draining``) but liveness stays 200 while shedding;
* ``GET /readyz`` — the load-balancer drain signal: **503** while the
  adaptive shed controller is actively shedding (or the engine is
  draining), 200 otherwise — a saturated replica gets routed around
  instead of hammered;
* ``GET /metrics`` — the process metrics registry as Prometheus text
  (same exposition ``obs.metrics.start_prometheus_server`` serves), so
  one port carries traffic AND its observability;
* ``GET /debug/traces[?limit=N]`` — recent request traces assembled into
  trees from the span ring (server → queue → fan-in batch → transform);
* ``GET /debug/slo`` — current burn rates per window, budget remaining,
  firing multi-window alerts from the engine's ``SloSet``, per-model
  circuit-breaker states, and the fault plane's armed faults (a chaos
  drill is auditable from the ops surface it is attacking);
* ``GET /debug/history`` — JSON range queries over the embedded
  time-series store (``obs.tsdb``): ``?name=<metric>&window=<s>`` for
  one family (``&rate=1`` adds reset-aware counter rate/delta), no
  ``name`` for the default bundle of key serve/SLO/device series the
  dashboard's sparklines plot (``start_serve_server`` starts the
  background sampler);
* ``POST /debug/profile?seconds=N`` — guarded on-demand device
  profiling (``obs.profiler``): single-flight, auto-stopped, lands
  ``jax.profiler`` + span-ring trace artifacts in the profile dir; a
  second start while one runs is **409**. ``GET /debug/profile`` shows
  the active/last capture;
* ``GET /debug/incidents`` — the auto-incident engine
  (``obs.incidents``): open + recent incidents with their on-disk
  evidence-bundle paths, lifecycle totals, and the detector catalog.
  ``start_serve_server`` installs the engine on the background sampler
  (env kill switch ``SPARK_RAPIDS_ML_TPU_OBS_INCIDENTS=0``), so
  detection runs at the sampling cadence with no extra thread;
* ``GET /debug/rollout`` + ``POST /debug/rollout/{promote,abort,
  canary}`` — the live-rollout control plane (``serve.rollout``):
  incumbent/candidate/canary state with per-arm live comparison, and
  the operator verbs (atomic warm-then-flip promotion, canary start,
  abort). Requires a ``RolloutController`` attached via
  ``engine.attach_rollout`` (409 otherwise);
* ``GET /dashboard`` — one self-contained HTML page polling those
  endpoints: the live ops view, with history sparklines and the
  incident timeline.

Threaded (one request per handler thread) — concurrency funnels into the
engine's micro-batchers, which is the whole point. The per-request
latency/counter metric family handles are resolved ONCE at handler-class
creation (the same convention as ``MicroBatcher._declare_metrics``), and
latency observations carry trace-id exemplars.
"""

from __future__ import annotations

import http.server
import json
import socketserver
import time
import urllib.parse
from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.obs import get_registry, tracectx
from spark_rapids_ml_tpu.obs import accounting as accounting_mod
from spark_rapids_ml_tpu.obs import federation as federation_mod
from spark_rapids_ml_tpu.obs import fitmon as fitmon_mod
from spark_rapids_ml_tpu.obs import forecast as forecast_mod
from spark_rapids_ml_tpu.obs import incidents as incidents_mod
from spark_rapids_ml_tpu.obs import profiler as profiler_mod
from spark_rapids_ml_tpu.obs import spans as spans_mod
from spark_rapids_ml_tpu.obs import tsdb as tsdb_mod
from spark_rapids_ml_tpu.serve.admission import ShedLoad
from spark_rapids_ml_tpu.serve.batching import (
    BatcherClosed,
    DeadlineExpired,
    QueueFull,
    WaitTimeout,
    WorkerCrashed,
)
from spark_rapids_ml_tpu.serve.breaker import BreakerOpen
from spark_rapids_ml_tpu.serve.engine import (
    EngineClosed,
    ServeEngine,
    publish_all_slos,
)
from spark_rapids_ml_tpu.serve.faults import fault_plane
from spark_rapids_ml_tpu.serve import wire

_MAX_BODY_BYTES = 64 * 1024 * 1024  # refuse absurd request bodies
_TRACE_ROOT_PREFIXES = ("serve:http", "serve:request")
_DEFAULT_TRACE_LIMIT = 20
_DEFAULT_HISTORY_WINDOW = 300.0
_MAX_HISTORY_WINDOW = 24 * 3600.0


def _json_safe(outputs: np.ndarray):
    return np.asarray(outputs).tolist()


def _query_float(params, key: str, default: float,
                 lo: float, hi: float) -> float:
    try:
        value = float(params.get(key, [default])[0])
    except (TypeError, ValueError):
        return default
    return min(max(value, lo), hi)


def history_document(params) -> dict:
    """The ``GET /debug/history`` body for parsed query params.

    ``?name=<metric>`` → every matching child series (``model=`` narrows
    by label, ``host=`` narrows federated fleet series to one peer,
    ``rate=1`` adds reset-aware rate/delta for counters); without
    ``name`` → the default bundle of key series the dashboard
    sparklines plot, plus sampler health."""
    store = tsdb_mod.get_tsdb()
    window = _query_float(params, "window", _DEFAULT_HISTORY_WINDOW,
                          1.0, _MAX_HISTORY_WINDOW)
    name = (params.get("name", [None])[0] or "").strip()
    model = (params.get("model", [None])[0] or "").strip()
    host = (params.get("host", [None])[0] or "").strip()
    labels = {}
    if model:
        labels["model"] = model
    if host:
        labels["host"] = host
    labels = labels or None
    if name:
        doc = {
            "name": name,
            "window": window,
            "series": store.range_query(name, labels, window),
        }
        if params.get("rate", [""])[0] in ("1", "true"):
            doc["rate_series"] = store.rate_points(name, labels, window)
            doc["rate_per_sec"] = store.rate(name, labels, window)
            doc["delta"] = store.delta(name, labels, window)
        return doc
    sampler = tsdb_mod.get_sampler()
    return {
        "window": window,
        "series_names": store.series_names(),
        "sampler": {
            "running": sampler.running,
            "interval_seconds": sampler.interval_seconds,
            "sweeps": sampler.sweeps,
            "series_count": store.series_count(),
            "dropped_series": store.dropped_series(),
        },
        "key": {
            "queue_depth": store.range_query(
                "sparkml_serve_queue_depth", None, window),
            "p99_latency_seconds": store.range_query(
                "sparkml_serve_request_latency_seconds",
                {"quantile": "0.99"}, window),
            "request_rate": store.rate_points(
                "sparkml_serve_requests_total", None, window),
            "requests_total": store.range_query(
                "sparkml_serve_requests_total", None, window),
            "device_mem_bytes_in_use": store.range_query(
                "sparkml_device_mem_bytes_in_use", None, window),
            "device_busy_rate": store.rate_points(
                "sparkml_serve_device_batch_seconds_total", None, window),
            "obs_overhead_rate": store.rate_points(
                "sparkml_obs_overhead_seconds_total", None, window),
            "slo_budget_remaining": store.range_query(
                "sparkml_slo_budget_remaining", None, window),
            # the per-model cost ledger (obs.accounting): residency by
            # component, device-time rate, and traffic temperature —
            # the dashboard's per-model sparklines
            "model_hbm_bytes": store.range_query(
                "sparkml_model_hbm_bytes", None, window),
            "model_device_rate": store.rate_points(
                "sparkml_model_device_seconds_total", None, window),
            "model_ewma_rps": store.range_query(
                "sparkml_model_ewma_rps", None, window),
            # canary per-arm vitals (serve.rollout publishes its private
            # arm sketches at tick cadence)
            "canary_arm_p99_seconds": store.range_query(
                "sparkml_serve_canary_arm_p99_seconds", None, window),
            "canary_arm_error_rate": store.range_query(
                "sparkml_serve_canary_arm_error_rate", None, window),
            # fleet federation + forecast (obs.federation/forecast):
            # per-host liveness and the predictive signal sparklines
            "fleet_host_up": store.range_query(
                "sparkml_fleet_host_up", None, window),
            "forecast_queue_wait_ms": store.range_query(
                "sparkml_forecast_queue_wait_ms", None, window),
            "forecast_rps": store.range_query(
                "sparkml_forecast_rps", None, window),
        },
    }




def make_handler(engine: ServeEngine):
    """The request-handler class bound to one engine instance."""

    # Metric family handles resolved once per handler class, NOT per
    # request — the hot path increments through closures.
    reg = get_registry()
    m_http_latency = reg.summary(
        "sparkml_http_request_latency_seconds",
        "HTTP front-end request latency by path and status "
        "(trace-id exemplars on the slowest requests)",
        ("path", "status"),
    )
    m_http_requests = reg.counter(
        "sparkml_http_requests_total",
        "HTTP front-end requests by path and status", ("path", "status"),
    )
    # /debug/slo totals: family handles summed per poll — an ops
    # endpoint hit hardest during an outage must not pay for a full
    # registry snapshot to read three counters.
    m_degraded = reg.counter(
        "sparkml_serve_degraded_total",
        "requests served by the degraded CPU fallback while the "
        "model's breaker was open", ("model",),
    )
    m_retries = reg.counter(
        "sparkml_serve_retries_total",
        "predict attempts re-entered after a transient backend "
        "failure", ("model",),
    )
    m_restarts = reg.counter(
        "sparkml_serve_worker_restarts_total",
        "batcher worker restarts after a crash or watchdog-declared "
        "wedge", ("model",),
    )

    class _Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # TCP_NODELAY: the response is two writes (headers, then body).
        # With Nagle on, a body smaller than the path MSS sits in the
        # kernel until the client ACKs the header segment — and a
        # client running delayed ACKs takes ~40 ms to do that. JSON
        # payloads are usually big enough to dodge it; the binary wire
        # responses (a few KB of packed rows) hit it dead on: measured
        # 48 ms p50 → 4 ms p50 on loopback with Nagle off. A serving
        # tier always trades this sliver of bandwidth for latency.
        disable_nagle_algorithm = True

        def _reply(self, status: int, payload: dict,
                   trace_ctx: Optional[tracectx.TraceContext] = None,
                   retry_after: Optional[float] = None,
                   ) -> int:
            body = json.dumps(payload).encode("utf-8")
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if retry_after is not None:
                # overload rejections (429/503/504) tell the caller WHEN
                # to come back — derived from the live queue-wait
                # estimate, not a constant
                self.send_header(
                    "Retry-After",
                    str(max(int(retry_after + 0.999), 1)))
            if trace_ctx is not None:
                self.send_header(tracectx.TRACEPARENT_HEADER,
                                 trace_ctx.traceparent())
            self.end_headers()
            self.wfile.write(body)
            return status

        def _drain_body(self) -> None:
            """Read (and discard) the request body without parsing it —
            replying before consuming the body would desync a keep-alive
            connection. A zero-length/absent body needs no drain and the
            connection stays open; an unparseable or oversize length
            closes it."""
            try:
                length = int(self.headers.get("Content-Length", 0) or 0)
            except (TypeError, ValueError):
                length = -1
            if 0 < length <= _MAX_BODY_BYTES:
                self.rfile.read(length)
            elif length != 0:
                self.close_connection = True

        def _reply_text(self, status: int, text: str,
                        content_type: str) -> int:
            body = text.encode("utf-8")
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return status

        def _reply_bytes(self, status: int, body: bytes,
                         content_type: str,
                         trace_ctx: Optional[tracectx.TraceContext] = None,
                         extra_headers: Optional[dict] = None) -> int:
            """A raw-bytes reply (the binary wire responses): explicit
            Content-Length like every other path, traceparent back, and
            the predict metadata as headers since a binary payload has
            no JSON fields to carry it."""
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            for key, value in (extra_headers or {}).items():
                self.send_header(key, str(value))
            if trace_ctx is not None:
                self.send_header(tracectx.TRACEPARENT_HEADER,
                                 trace_ctx.traceparent())
            self.end_headers()
            self.wfile.write(body)
            return status

        def do_GET(self):  # noqa: N802 - http.server API
            parsed = urllib.parse.urlparse(self.path)
            path = parsed.path
            if path == "/healthz":
                # liveness stays 200 even while shedding (the process is
                # alive and answering); the STATUS FIELD carries the
                # overload posture so anything reading /healthz sees it.
                # shed_posture refreshes the controller's timeline: a
                # drained replica has no predict traffic, so probes are
                # what keep de-escalation possible.
                shed = engine.shed_posture()
                status = self._reply(200, {
                    "status": ("draining" if engine._closed
                               else "shedding" if shed.shedding()
                               else "ok"),
                    "models": engine.registry.names(),
                    "queue_depth": engine.queue_depth(),
                    "shed_level": shed.level(),
                    "inflight": tracectx.inflight_requests(),
                })
            elif path == "/readyz":
                # the load-balancer drain signal: a saturated replica
                # that is actively shedding answers 503 here so the LB
                # routes around it instead of hammering it — while
                # /healthz keeps reporting the process alive. Probe
                # reads refresh the controller (engine.shed_posture), so
                # a drained replica cools down and re-enters rotation.
                shedding = engine.shed_posture().shedding()
                overload = engine.overload_state()

                # the replica tier's contract: /readyz stays 200 while
                # >= 1 replica is healthy — a sick device DRAINS onto
                # its siblings, it does not take the tier out of
                # rotation (only shedding/closing does). Computed
                # lazily: probes hit this at ~1 Hz and the snapshot
                # walks every replica's locks — the closed branch must
                # not pay for a summary it discards.
                def replica_health() -> dict:
                    replicas = engine.replica_snapshot()
                    return {
                        "healthy": sum(doc["healthy"]
                                       for doc in replicas.values()),
                        "total": sum(doc["total"]
                                     for doc in replicas.values()),
                    }

                if engine._closed:
                    status = self._reply(
                        503, {"status": "draining", "ready": False})
                elif shedding:
                    status = self._reply(503, {
                        "status": "shedding", "ready": False,
                        "shed_level": overload["shed"]["level"],
                        "overload": overload["shed"]["signals"],
                        "replicas": replica_health(),
                    }, retry_after=overload["retry_after_seconds"])
                else:
                    health = replica_health()
                    if health["total"] > 0 and health["healthy"] == 0:
                        # the other half of the replica contract:
                        # EVERY replica draining/dead means the tier
                        # can only answer via the degraded fallback —
                        # the LB should prefer a replica that can
                        # actually reach a device (probes keep hitting
                        # this endpoint, and the half-open re-entry
                        # flips it back to 200)
                        status = self._reply(503, {
                            "status": "unhealthy", "ready": False,
                            "replicas": health,
                        }, retry_after=overload["retry_after_seconds"])
                    else:
                        status = self._reply(200, {
                            "status": "ready", "ready": True,
                            "models": engine.registry.names(),
                            "replicas": health,
                        })
            elif path == "/metrics":
                status = self._reply_text(
                    200, get_registry().prometheus_text(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            elif path == "/debug/traces":
                params = urllib.parse.parse_qs(parsed.query)
                trace_id = (params.get("trace_id", [None])[0]
                            or "").strip()
                if trace_id:
                    # single-trace lookup: the resolver for the
                    # trace-id exemplars /metrics and the quantile
                    # snapshots already emit
                    tree = spans_mod.assemble_trace(trace_id)
                    if tree.get("span_count"):
                        status = self._reply(200, tree)
                    else:
                        status = self._reply(404, {
                            "error": "unknown trace_id (not in the "
                                     "span ring, or already evicted)",
                            "trace_id": trace_id,
                        })
                else:
                    try:
                        limit = int(params.get(
                            "limit", [_DEFAULT_TRACE_LIMIT])[0])
                    except (TypeError, ValueError):
                        limit = _DEFAULT_TRACE_LIMIT
                    summaries = spans_mod.recent_traces(
                        max(1, min(limit, 200)),
                        name_prefix=_TRACE_ROOT_PREFIXES,
                    )
                    status = self._reply(200, {
                        "traces": [
                            spans_mod.assemble_trace(s["trace_id"])
                            for s in summaries
                        ],
                    })
            elif path == "/debug/slo":
                snap = engine.slo_snapshot()
                snap["queue_depth"] = engine.queue_depth()
                snap["models"] = engine.registry.names()
                snap["closed"] = engine._closed
                snap["breakers"] = engine.breaker_snapshot()
                snap["faults"] = fault_plane().active()
                snap["degraded_total"] = m_degraded.total()
                snap["retries_total"] = m_retries.total()
                snap["worker_restarts_total"] = m_restarts.total()
                snap["overload"] = engine.overload_state()
                snap["replicas"] = engine.replica_snapshot()
                snap["rollout"] = engine.rollout_snapshot()
                snap["autoscale"] = engine.autoscale_snapshot()
                snap["tiering"] = engine.tiering_snapshot()
                status = self._reply(200, snap)
            elif path == "/debug/history":
                params = urllib.parse.parse_qs(parsed.query)
                status = self._reply(200, history_document(params))
            elif path == "/debug/profile":
                status = self._reply(200, {
                    "active": profiler_mod.capture_active(),
                    "last": profiler_mod.last_capture(),
                    "dir": profiler_mod.profile_dir(),
                })
            elif path == "/debug/incidents":
                status = self._reply(
                    200,
                    incidents_mod.get_incident_engine().snapshot(),
                )
            elif path == "/debug/rollout":
                status = self._reply(200, engine.rollout_snapshot())
            elif path == "/debug/autoscale":
                status = self._reply(200, engine.autoscale_snapshot())
            elif path == "/debug/tiering":
                status = self._reply(200, engine.tiering_snapshot())
            elif path == "/debug/costs":
                status = self._reply(200, engine.costs_snapshot())
            elif path == "/debug/fit":
                status = self._reply(200, fitmon_mod.debug_fit_doc())
            elif path == "/debug/fleet/export":
                params = urllib.parse.parse_qs(parsed.query)
                cursor = _query_float(params, "cursor", 0.0,
                                      0.0, float("inf"))
                status = self._reply(200, federation_mod.fleet_export(
                    cursor, engine=engine))
            elif path == "/debug/fleet":
                aggregator = federation_mod.get_aggregator()
                doc = {
                    "host": federation_mod.host_identity(),
                    "aggregating": aggregator is not None,
                    "rollup": (aggregator.rollup()
                               if aggregator is not None else None),
                }
                if (aggregator is None
                        or aggregator.forecaster is None):
                    doc["forecast"] = (
                        forecast_mod.get_forecaster().snapshot())
                status = self._reply(200, doc)
            elif path == "/dashboard":
                status = self._reply_text(
                    200, DASHBOARD_HTML, "text/html; charset=utf-8")
            else:
                status = self._reply(404,
                                     {"error": f"unknown path {path!r}"})
                # arbitrary client URLs must not mint unbounded metric
                # children (classic label-cardinality leak)
                path = "(unknown)"
            m_http_requests.inc(path=path, status=str(status))

        def do_POST(self):  # noqa: N802 - http.server API
            parsed = urllib.parse.urlparse(self.path)
            path = parsed.path
            if path == "/debug/profile":
                status = self._handle_profile(parsed)
                m_http_requests.inc(path=path, status=str(status))
                return
            if path in ("/debug/rollout/promote", "/debug/rollout/abort",
                        "/debug/rollout/canary"):
                status = self._handle_rollout(parsed, path)
                m_http_requests.inc(path=path, status=str(status))
                return
            if path != "/predict":
                status = self._reply(404,
                                     {"error": f"unknown path {path!r}"})
                m_http_requests.inc(path="(unknown)", status=str(status))
                return
            # Honor an inbound W3C traceparent (continue the caller's
            # trace; our root span's parent is the caller's span id), or
            # mint a fresh root for header-less traffic.
            inbound = tracectx.parse_traceparent(
                self.headers.get(tracectx.TRACEPARENT_HEADER))
            ctx = inbound if inbound is not None else tracectx.new_context()
            t0 = time.perf_counter()
            with tracectx.activate(ctx), spans_mod.span(
                "serve:http:predict", trace_id=ctx.trace_id,
            ):
                status = self._handle_predict(ctx)
            m_http_latency.observe(
                time.perf_counter() - t0, trace_id=ctx.trace_id,
                path=path, status=str(status),
            )
            m_http_requests.inc(path=path, status=str(status))

        def _handle_profile(self, parsed) -> int:
            """``POST /debug/profile?seconds=N``: start a single-flight
            on-demand capture (``obs.profiler``). 200 with the capture
            info; 409 while one is already running."""
            # Parameters ride the query string, but clients may still
            # POST a body (curl -d '{}') — drain it, or a keep-alive
            # connection parses the leftover bytes as its next request.
            self._drain_body()
            params = urllib.parse.parse_qs(parsed.query)
            seconds = _query_float(params, "seconds", 5.0,
                                   0.05, profiler_mod.MAX_SECONDS)
            label = (params.get("label", ["ondemand"])[0]
                     or "ondemand")
            try:
                info = profiler_mod.start_capture(seconds, label=label)
            except profiler_mod.CaptureInFlight as exc:
                return self._reply(409, {
                    "error": str(exc),
                    "active": profiler_mod.capture_active(),
                })
            except Exception as exc:  # noqa: BLE001 - surface, don't die
                return self._reply(500, {
                    "error": f"{type(exc).__name__}: {exc}"
                })
            return self._reply(200, {"started": info})

        def _handle_rollout(self, parsed, path: str) -> int:
            """``POST /debug/rollout/{promote,abort,canary}`` — the
            rollout control plane's operator verbs. ``promote``
            hot-swaps the alias to ``?version=N`` (or the live
            candidate), ``abort`` ends the canary without judgment,
            ``canary`` starts an experiment (``?version=N&fraction=F``).
            409 without an attached controller."""
            self._drain_body()
            controller = engine.rollout_controller()
            if controller is None:
                return self._reply(409, {
                    "error": "no rollout controller attached to this "
                             "engine (serve.rollout.RolloutController + "
                             "engine.attach_rollout)",
                })
            params = urllib.parse.parse_qs(parsed.query)
            version_raw = (params.get("version", [None])[0] or "").strip()
            version = None
            if version_raw:
                try:
                    version = int(version_raw)
                except ValueError:
                    return self._reply(400, {
                        "error": f"bad version {version_raw!r}"})
            try:
                if path.endswith("/promote"):
                    promoted = controller.promote(version)
                    doc = {"promoted": promoted}
                elif path.endswith("/abort"):
                    reason = (params.get("reason", ["operator"])[0]
                              or "operator")
                    doc = {"aborted": controller.abort(reason=reason)}
                else:
                    fraction = params.get("fraction", [None])[0]
                    doc = {"canary": controller.start_canary(
                        version,
                        fraction=(float(fraction)
                                  if fraction else None))}
            except KeyError as exc:
                return self._reply(404, {"error": str(exc)})
            except ValueError as exc:
                return self._reply(400, {"error": str(exc)})
            except Exception as exc:  # noqa: BLE001 - surface, don't die
                return self._reply(500, {
                    "error": f"{type(exc).__name__}: {exc}"})
            doc["rollout"] = engine.rollout_snapshot()
            return self._reply(200, doc)

        def _handle_predict(self, ctx: tracectx.TraceContext) -> int:
            """Parse, predict, reply; returns the HTTP status it sent.
            Every reply — 200 and all error paths (400/404/429/503/504)
            — goes through ``_reply``, so every response carries an
            explicit ``Content-Length`` and the ``traceparent``."""
            # Pre-parse fast path: when the shed controller is already
            # rejecting this (header-identified) tenant/priority class,
            # say no BEFORE paying the JSON body parse — under a reject
            # storm, the cost of a rejection decides whether rejecting
            # frees capacity or re-spends it. The body is drained raw
            # (keep-alive must not desync) but never parsed.
            shed_exc = engine.fast_shed(self.headers.get("X-Tenant"),
                                        self.headers.get("X-Priority"))
            if shed_exc is not None:
                self._drain_body()
                return self._reply(503, {
                    "error": str(shed_exc),
                    "retryable": True,
                    "shed": True,
                    "reason": shed_exc.reason,
                }, trace_ctx=ctx, retry_after=shed_exc.retry_after)
            try:
                length = int(self.headers.get("Content-Length", 0))
                if length <= 0 or length > _MAX_BODY_BYTES:
                    raise ValueError(f"bad Content-Length {length}")
                raw = self.rfile.read(length)
            except (TypeError, ValueError) as exc:
                # Nothing (or garbage) was read — a keep-alive
                # connection would desync, so close it.
                self.close_connection = True
                return self._reply(400, {"error": f"bad request: {exc}"},
                                   trace_ctx=ctx)
            try:
                # ALL body decoding routes through serve.wire (rule 11):
                # binary columnar when the Content-Type negotiates it,
                # the JSON text protocol otherwise — both recording the
                # parse-phase latency the wire bench judges.
                req = wire.decode_body(
                    raw, self.headers.get("Content-Type"),
                    trace_id=ctx.trace_id)
            except wire.WireError as exc:
                if exc.kind == "binary":
                    # the full body was already read above, so the
                    # connection stays in sync — no close needed; the
                    # decode already counted the distinct bad_wire label
                    return self._reply(exc.status, {
                        "error": f"bad wire body: {exc}",
                        "reason": exc.reason,
                    }, trace_ctx=ctx)
                # JSON parse errors keep the PR 4 bad-request semantics
                self.close_connection = True
                return self._reply(400, {"error": f"bad request: {exc}"},
                                   trace_ctx=ctx)
            # tenant/priority: HEADERS win over body fields — the
            # pre-parse fast-shed path above can only see headers, so
            # headers must be authoritative or a fast shed and a full
            # admission could judge the same request as two different
            # tenants. Body fields are the fallback for header-less
            # JSON clients; binary bodies are header-only by design.
            tenant = self.headers.get("X-Tenant") or req.tenant
            priority = self.headers.get("X-Priority") or req.priority
            binary_out = wire.wants_binary_response(
                self.headers.get("Accept"), req.binary)
            served = {}
            try:
                # Resolve once — through the rollout tier's canary
                # router — and predict against the PINNED version, so
                # the reported version is the one that actually served
                # the request even if a concurrent register() bumps
                # "latest". Canary-routed requests pin to the shadow
                # tenant (when configured) so the fairness ledger
                # audits the experiment as its own tenant.
                entry, canary_tenant = engine.route_entry(
                    req.model, trace_id=ctx.trace_id)
                if canary_tenant:
                    tenant = canary_tenant
                # error replies carry the version that failed the
                # request: during a canary, "which arm broke" must be
                # readable from the wire
                served = {"model": entry.name, "version": entry.version}
                result = engine.predict_detailed(
                    entry.name, req.rows, version=entry.version,
                    deadline_ms=req.deadline_ms,
                    tenant=tenant, priority=priority,
                )
            except KeyError as exc:
                return self._reply(404, {"error": str(exc)}, trace_ctx=ctx)
            except ValueError as exc:
                # request-shape errors (empty / oversize batch) are the
                # client's to fix
                return self._reply(400, {"error": str(exc), **served},
                                   trace_ctx=ctx)
            except QueueFull as exc:
                return self._reply(
                    429, {"error": str(exc), **served}, trace_ctx=ctx,
                    retry_after=engine.retry_after_estimate())
            except ShedLoad as exc:
                # the adaptive overload controller's verdict: distinct
                # from QueueFull (the queue may not even be full), with
                # the controller's own Retry-After estimate
                return self._reply(503, {
                    "error": str(exc),
                    "retryable": True,
                    "shed": True,
                    "reason": exc.reason,
                    **served,
                }, trace_ctx=ctx, retry_after=exc.retry_after)
            except (DeadlineExpired, WaitTimeout) as exc:
                return self._reply(
                    504, {"error": str(exc), **served}, trace_ctx=ctx,
                    retry_after=engine.retry_after_estimate())
            except (BreakerOpen, WorkerCrashed) as exc:
                # self-healing states: the breaker is shedding for this
                # model / the worker is being restarted — retryable 503
                # (and never a hang: both fail fast by construction)
                return self._reply(503, {
                    "error": str(exc),
                    "retryable": True,
                    **served,
                }, trace_ctx=ctx,
                    retry_after=engine.retry_after_estimate())
            except (BatcherClosed, EngineClosed) as exc:
                # both mean "shutting down" — retryable 503, not a 5xx page
                return self._reply(503, {"error": str(exc), **served},
                                   trace_ctx=ctx)
            except Exception as exc:  # noqa: BLE001 - surface, don't die
                return self._reply(500, {
                    "error": f"{type(exc).__name__}: {exc}",
                    **served,
                }, trace_ctx=ctx)
            if binary_out:
                # metadata travels as headers — the payload is pure rows
                return self._reply_bytes(
                    200, wire.encode_response(result.outputs),
                    wire.BINARY_CONTENT_TYPE, trace_ctx=ctx,
                    extra_headers={
                        "X-Model": entry.name,
                        "X-Model-Version": entry.version,
                        "X-Trace-Id": ctx.trace_id,
                        "X-Degraded": int(result.degraded),
                        "X-Retries": result.retries,
                    })
            return self._reply(200, {
                "model": entry.name,
                "version": entry.version,
                "outputs": _json_safe(result.outputs),
                "trace_id": ctx.trace_id,
                "degraded": result.degraded,
                "retries": result.retries,
            }, trace_ctx=ctx)

        def log_message(self, *args):  # silence per-request stderr noise
            pass

    return _Handler


class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    # Overload survival: a shedding server churns connections far faster
    # than socketserver's default 5-deep accept backlog — once the SYN
    # queue overflows, clients silently sit in kernel retransmit
    # (1+2+4+8… seconds) and the in-SLO tenant's tail blows up exactly
    # when the application layer is shedding to stay fast. Measured
    # directly in scripts/load_harness.py: compliant p99 went from ~15 s
    # (the retransmit ladder) to the queue-wait target after this.
    request_queue_size = 128


def start_serve_server(
    engine: ServeEngine, port: int = 0, addr: str = "127.0.0.1",
) -> http.server.HTTPServer:
    """Serve the engine on a daemon thread; returns the HTTPServer (bind
    ``port=0`` for ephemeral — read ``server.server_address[1]``; stop
    with ``server.shutdown()``, then ``engine.shutdown()`` to drain).
    Also starts the background history sampler (``obs.tsdb``;
    process-wide, outlives this server and joins itself at exit) so
    ``/debug/history`` and the dashboard sparklines have data, and —
    unless ``SPARK_RAPIDS_ML_TPU_OBS_INCIDENTS=0`` — installs the
    auto-incident engine on it: detectors run at the sampling cadence
    on the sampler's own thread, and the SLO gauges are republished
    every sweep so the fast-burn detector reads live values."""
    sampler = tsdb_mod.start_sampling()
    # SLO gauges republish every sweep REGARDLESS of the incident kill
    # switch: turning off auto-incidents must not freeze the burn-rate
    # history the dashboard and /debug/history plot.
    sampler.register_collector(publish_all_slos)
    # the cost ledger's time-derived gauges (last-hit age, EWMA rps)
    # refresh every sweep, so the per-model series get history even
    # when nobody polls /debug/costs
    sampler.register_collector(accounting_mod.get_ledger().publish)
    if incidents_mod.enabled():
        incidents_mod.get_incident_engine().install(sampler)
    # republish the engine's live queue-wait estimate as a gauge every
    # sweep: the forecaster's input series (obs.forecast) and the
    # /debug/history queue-wait sparkline — the overload signal itself
    # is computed on demand and would otherwise never earn history
    g_queue_wait = get_registry().gauge(
        forecast_mod.QUEUE_WAIT_SERIES,
        "the live queue-wait EWMA (the autoscale/shed signal), "
        "republished every sampler sweep for history + forecasting",
    )

    m_collector_errors = get_registry().counter(
        "sparkml_serve_collector_errors_total",
        "sampler collector callbacks that raised (and were swallowed "
        "so the sweep survives)",
        ("collector",),
    )

    def _publish_queue_wait():
        try:
            g_queue_wait.set(float(
                engine._overload_signals().get("queue_wait_s", 0.0)))
        except Exception:  # noqa: BLE001 - a collector must not kill sweeps
            m_collector_errors.inc(collector="queue_wait")

    sampler.register_collector(_publish_queue_wait)
    # the short-horizon forecaster rides the same sweep (kill switch
    # SPARK_RAPIDS_ML_TPU_FORECAST=0 leaves it installed but inert)
    forecast_mod.get_forecaster().install(sampler)
    server = _Server((addr, port), make_handler(engine))
    thread = tracectx.traced_thread(
        server.serve_forever, name="sparkml-serve-http", daemon=True,
        fresh=True,
    )
    thread.start()
    return server


# -- the live ops dashboard --------------------------------------------------
#
# One self-contained page, zero external assets: stat tiles + tables over
# /healthz, /debug/slo, and /debug/traces. Status colors are the reserved
# status palette and always ship with an icon + label (never color alone);
# text wears text tokens; light/dark are both selected via custom props.

DASHBOARD_HTML = """<!DOCTYPE html>
<html lang="en">
<head>
<meta charset="utf-8">
<meta name="viewport" content="width=device-width, initial-scale=1">
<title>spark_rapids_ml_tpu · serving ops</title>
<style>
  .viz-root {
    color-scheme: light;
    --surface-1: #fcfcfb;
    --surface-2: #f0efec;
    --text-primary: #0b0b0b;
    --text-secondary: #52514e;
    --status-good: #0ca30c;
    --status-warning: #fab219;
    --status-serious: #ec835a;
    --status-critical: #d03b3b;
    --border: #d9d8d4;
    --series-1: #2a78d6;
  }
  @media (prefers-color-scheme: dark) {
    :root:where(:not([data-theme="light"])) .viz-root {
      color-scheme: dark;
      --surface-1: #1a1a19;
      --surface-2: #383835;
      --text-primary: #ffffff;
      --text-secondary: #c3c2b7;
      --border: #44443f;
      --series-1: #3987e5;
    }
  }
  :root[data-theme="dark"] .viz-root {
    color-scheme: dark;
    --surface-1: #1a1a19;
    --surface-2: #383835;
    --text-primary: #ffffff;
    --text-secondary: #c3c2b7;
    --border: #44443f;
    --series-1: #3987e5;
  }
  body { margin: 0; }
  .viz-root {
    font: 14px/1.45 system-ui, -apple-system, "Segoe UI", sans-serif;
    background: var(--surface-1); color: var(--text-primary);
    min-height: 100vh; padding: 20px 24px; box-sizing: border-box;
  }
  h1 { font-size: 17px; font-weight: 600; margin: 0 0 2px; }
  h2 { font-size: 13px; font-weight: 600; margin: 22px 0 8px;
       color: var(--text-secondary); text-transform: uppercase;
       letter-spacing: 0.04em; }
  .sub { color: var(--text-secondary); margin: 0 0 18px; }
  .tiles { display: flex; flex-wrap: wrap; gap: 12px; }
  .tile { background: var(--surface-2); border-radius: 8px;
          padding: 12px 16px; min-width: 150px; }
  .tile .label { color: var(--text-secondary); font-size: 12px; }
  .tile .value { font-size: 26px; font-weight: 600; margin-top: 2px; }
  table { border-collapse: collapse; width: 100%; }
  th { text-align: left; color: var(--text-secondary); font-weight: 500;
       font-size: 12px; border-bottom: 1px solid var(--border);
       padding: 4px 10px 4px 0; }
  td { padding: 5px 10px 5px 0; border-bottom: 1px solid var(--border);
       font-variant-numeric: tabular-nums; }
  td.name { font-variant-numeric: normal; }
  .status { display: inline-flex; align-items: center; gap: 6px; }
  .dot { width: 9px; height: 9px; border-radius: 50%; display: inline-block; }
  .good .dot { background: var(--status-good); }
  .warning .dot { background: var(--status-warning); }
  .serious .dot { background: var(--status-serious); }
  .critical .dot { background: var(--status-critical); }
  .mono { font-family: ui-monospace, monospace; font-size: 12px; }
  details { margin: 4px 0; }
  summary { cursor: pointer; color: var(--text-secondary); }
  pre { background: var(--surface-2); border-radius: 6px; padding: 10px;
        overflow-x: auto; font-size: 11px; }
  .quiet { color: var(--text-secondary); }
  svg.spark { display: block; margin-top: 6px; overflow: visible; }
  svg.spark polyline { stroke: var(--series-1); fill: none;
       stroke-width: 2; stroke-linejoin: round; stroke-linecap: round; }
  svg.spark circle { fill: var(--series-1); }
  #tip { position: fixed; display: none; pointer-events: none;
       background: var(--surface-2); color: var(--text-primary);
       border: 1px solid var(--border); border-radius: 4px;
       padding: 2px 7px; font-size: 11px; z-index: 10;
       font-variant-numeric: tabular-nums; }
</style>
</head>
<body>
<div class="viz-root">
  <h1>Serving ops</h1>
  <p class="sub">live view over <span class="mono">/debug/slo</span>,
    <span class="mono">/debug/history</span>,
    <span class="mono">/debug/incidents</span>,
    <span class="mono">/debug/traces</span>, and
    <span class="mono">/healthz</span> · refreshes every 2&thinsp;s</p>
  <div class="tiles" id="tiles"></div>
  <h2>Metrics history · last 5 min</h2>
  <div class="tiles" id="history">—</div>
  <div id="tip"></div>
  <h2>SLO burn rates</h2>
  <table><thead><tr><th>Objective</th><th>Target</th><th>5m</th><th>30m</th>
    <th>1h</th><th>6h</th><th>Budget left</th><th>State</th></tr></thead>
    <tbody id="slo-rows"></tbody></table>
  <h2>Fleet</h2>
  <div id="fleet" class="quiet">—</div>
  <h2>Serving replicas</h2>
  <div id="replicas" class="quiet">—</div>
  <h2>Fit runs</h2>
  <div id="fit" class="quiet">—</div>
  <h2>Incidents</h2>
  <div id="incidents" class="quiet">—</div>
  <h2>Circuit breakers</h2>
  <div id="breakers" class="quiet">—</div>
  <h2>Firing alerts</h2>
  <div id="alerts" class="quiet">—</div>
  <h2>Recent traces</h2>
  <div id="traces" class="quiet">—</div>
</div>
<script>
function fmtPct(v) {
  return (v == null) ? "–" : (100 * v).toFixed(2) + "%";
}
function fmtBurn(v) {
  return (v == null) ? "–" : v.toFixed(2);
}
function fmtBytes(v) {
  if (v == null) return "–";
  var units = ["B", "KiB", "MiB", "GiB", "TiB"], i = 0;
  while (v >= 1024 && i < units.length - 1) { v /= 1024; i += 1; }
  return v.toFixed(v >= 10 || i === 0 ? 0 : 1) + " " + units[i];
}
function stateFor(slo) {
  if (slo.alerts.some(a => a.severity === "page_fast"))
    return ["critical", "\\u25cf paging (fast)"];
  if (slo.alerts.length) return ["serious", "\\u25cf paging (slow)"];
  var rates = Object.values(slo.burn_rates || {});
  if (rates.some(r => r > 1)) return ["warning", "\\u25cf burning budget"];
  return ["good", "\\u25cf within budget"];
}
function tile(label, value, trend) {
  return '<div class="tile"><div class="label">' + label +
    '</div><div class="value">' + value + "</div>" + (trend || "") +
    "</div>";
}
function fmtVal(v) {
  if (v == null || !isFinite(v)) return "\\u2013";
  var a = Math.abs(v);
  if (a >= 1e9) return (v / 1e9).toFixed(1) + "G";
  if (a >= 1e6) return (v / 1e6).toFixed(1) + "M";
  if (a >= 1e3) return (v / 1e3).toFixed(1) + "K";
  if (a >= 100) return v.toFixed(0);
  if (a >= 1) return v.toFixed(2);
  if (a === 0) return "0";
  return v.toPrecision(3);
}
var SPARK_W = 150, SPARK_H = 36;
function sparkSvg(points) {
  // one series per sparkline (the tile label names it — no legend);
  // 2px line in --series-1, last point dotted, values live in #tip
  if (!points || points.length < 2)
    return '<div class="spark quiet" style="height:' + SPARK_H +
      'px;font-size:11px;margin-top:6px">collecting\\u2026</div>';
  var t0 = points[0][0], t1 = points[points.length - 1][0];
  var vs = points.map(function (p) { return p[1]; });
  var lo = Math.min.apply(null, vs), hi = Math.max.apply(null, vs);
  if (hi === lo) hi = lo + 1;
  var pad = 3;
  function xy(p) {
    var x = pad + (SPARK_W - 2 * pad) *
      (t1 === t0 ? 1 : (p[0] - t0) / (t1 - t0));
    var y = pad + (SPARK_H - 2 * pad) * (1 - (p[1] - lo) / (hi - lo));
    return [x, y];
  }
  var line = points.map(function (p) {
    var c = xy(p);
    return c[0].toFixed(1) + "," + c[1].toFixed(1);
  }).join(" ");
  var last = xy(points[points.length - 1]);
  return '<svg class="spark" width="' + SPARK_W + '" height="' +
    SPARK_H + '" data-points=\\'' + JSON.stringify(points) +
    '\\' role="img"><polyline points="' + line + '"/><circle cx="' +
    last[0].toFixed(1) + '" cy="' + last[1].toFixed(1) +
    '" r="2.5"/></svg>';
}
function seriesLabel(prefix, labels) {
  var parts = [];
  ["model", "device", "component", "arm", "outcome", "host",
   "horizon"].forEach(
    function (k) {
      if (labels && labels[k]) parts.push(labels[k]);
    });
  return prefix + (parts.length ? " \\u00b7 " + parts.join(" / ") : "");
}
function trendTile(prefix, series, fmt) {
  var pts = series.points || [];
  var cur = pts.length ? pts[pts.length - 1][1] : null;
  return tile(seriesLabel(prefix, series.labels),
              (fmt || fmtVal)(cur), sparkSvg(pts));
}
function historyTiles(hist) {
  var key = (hist && hist.key) || {};
  var tiles = [];
  (key.queue_depth || []).forEach(function (s) {
    tiles.push(trendTile("queue depth", s));
  });
  (key.p99_latency_seconds || []).forEach(function (s) {
    tiles.push(trendTile("p99 latency", s, function (v) {
      return v == null ? "\\u2013" : (1000 * v).toFixed(1) + " ms";
    }));
  });
  (key.request_rate || []).forEach(function (s) {
    if (s.labels && s.labels.outcome && s.labels.outcome !== "ok")
      return;  // error outcomes live in the SLO table
    tiles.push(trendTile("req/s", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + "/s";
    }));
  });
  (key.device_mem_bytes_in_use || []).forEach(function (s) {
    tiles.push(trendTile("mem in use", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + "B";
    }));
  });
  (key.device_busy_rate || []).forEach(function (s) {
    tiles.push(trendTile("device busy", s, function (v) {
      return v == null ? "\\u2013" : (100 * v).toFixed(1) + "%";
    }));
  });
  (key.obs_overhead_rate || []).forEach(function (s) {
    tiles.push(trendTile("obs overhead", s, function (v) {
      return v == null ? "\\u2013" : (100 * v).toFixed(2) + "%";
    }));
  });
  // the per-model cost ledger (/debug/costs): residency by component,
  // attributed device time, traffic temperature
  (key.model_hbm_bytes || []).forEach(function (s) {
    tiles.push(trendTile("model HBM", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + "B";
    }));
  });
  (key.model_device_rate || []).forEach(function (s) {
    tiles.push(trendTile("model device", s, function (v) {
      return v == null ? "\\u2013" : (100 * v).toFixed(1) + "%";
    }));
  });
  (key.model_ewma_rps || []).forEach(function (s) {
    tiles.push(trendTile("model rows/s", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + "/s";
    }));
  });
  // canary per-arm sparklines (candidate vs incumbent)
  (key.canary_arm_p99_seconds || []).forEach(function (s) {
    tiles.push(trendTile("canary p99", s, function (v) {
      return v == null ? "\\u2013" : (1000 * v).toFixed(1) + " ms";
    }));
  });
  (key.canary_arm_error_rate || []).forEach(function (s) {
    tiles.push(trendTile("canary err", s, function (v) {
      return v == null ? "\\u2013" : (100 * v).toFixed(2) + "%";
    }));
  });
  // fleet liveness + the forecaster's predictive signals
  (key.fleet_host_up || []).forEach(function (s) {
    tiles.push(trendTile("host up", s));
  });
  (key.forecast_queue_wait_ms || []).forEach(function (s) {
    tiles.push(trendTile("fc queue wait", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + " ms";
    }));
  });
  (key.forecast_rps || []).forEach(function (s) {
    tiles.push(trendTile("fc req/s", s, function (v) {
      return v == null ? "\\u2013" : fmtVal(v) + "/s";
    }));
  });
  return tiles;
}
document.addEventListener("mousemove", function (e) {
  var tip = document.getElementById("tip");
  var svg = e.target && e.target.closest
    ? e.target.closest("svg.spark") : null;
  if (!svg) { if (tip) tip.style.display = "none"; return; }
  var points = [];
  try { points = JSON.parse(svg.getAttribute("data-points")); }
  catch (err) { return; }
  if (!points.length) return;
  var rect = svg.getBoundingClientRect();
  var frac = Math.min(Math.max(
    (e.clientX - rect.left) / rect.width, 0), 1);
  var idx = Math.round(frac * (points.length - 1));
  var p = points[idx];
  var ago = Math.max(0, Date.now() / 1000 - p[0]);
  tip.textContent = fmtVal(p[1]) + " \\u00b7 " +
    (ago < 120 ? ago.toFixed(0) + " s ago"
               : (ago / 60).toFixed(1) + " min ago");
  tip.style.left = (e.clientX + 12) + "px";
  tip.style.top = (e.clientY + 12) + "px";
  tip.style.display = "block";
});
function statusSpan(cls, text) {
  return '<span class="status ' + cls + '"><span class="dot"></span>' +
    text.replace("\\u25cf ", "") + "</span>";
}
function fmtAgo(ts) {
  if (ts == null) return "\\u2013";
  var ago = Math.max(0, Date.now() / 1000 - ts);
  if (ago < 120) return ago.toFixed(0) + " s ago";
  if (ago < 7200) return (ago / 60).toFixed(1) + " min ago";
  return (ago / 3600).toFixed(1) + " h ago";
}
function severityClass(sev) {
  if (sev === "critical") return "critical";
  if (sev === "serious") return "serious";
  return "warning";
}
function incidentRows(list, state) {
  return list.map(function (inc) {
    var labels = Object.keys(inc.labels || {}).map(function (k) {
      return k + "=" + inc.labels[k];
    }).join(" ");
    return "<tr><td class=name>" + inc.detector +
      (labels ? " \\u00b7 " + labels : "") + "</td><td>" +
      statusSpan(state === "open" ? severityClass(inc.severity)
                                  : "good",
                 "\\u25cf " + inc.severity +
                 (state === "open" ? "" : " (resolved)")) +
      "</td><td>" + fmtAgo(inc.opened_ts) + "</td><td>" +
      (inc.duration_seconds == null ? "\\u2013"
        : inc.duration_seconds.toFixed(0) + " s") +
      "</td><td>" + fmtVal(inc.value) + " vs " +
      fmtVal(inc.baseline) + "</td><td class=name><span class=mono>" +
      ((inc.evidence || {}).dir || "\\u2013") + "</span></td></tr>";
  }).join("");
}
function sumSeries(seriesList) {
  // point-wise sum across children keyed by sample timestamp (every
  // child shares the sampler's sweep timestamps) — the engine-wide
  // overview tile must trend the SUM, not whichever model's series
  // happened to come back first
  var byTs = {};
  seriesList.forEach(function (s) {
    (s.points || []).forEach(function (p) {
      byTs[p[0]] = (byTs[p[0]] || 0) + p[1];
    });
  });
  return Object.keys(byTs).map(function (t) { return parseFloat(t); })
    .sort(function (a, b) { return a - b; })
    .map(function (t) { return [t, byTs[t]]; });
}
async function refresh() {
  try {
    var slo = await (await fetch("/debug/slo")).json();
    var health = await (await fetch("/healthz")).json();
    var hist = {};
    try { hist = await (await fetch("/debug/history")).json(); }
    catch (err) { hist = {}; }
    var inc = {};
    try { inc = await (await fetch("/debug/incidents")).json(); }
    catch (err) { inc = {}; }
    var fit = {};
    try { fit = await (await fetch("/debug/fit")).json(); }
    catch (err) { fit = {}; }
    var incOpen = inc.open || [], incRecent = inc.recent || [];
    var qdSeries = ((hist.key || {}).queue_depth || []);
    var qdPoints = qdSeries.length ? sumSeries(qdSeries) : null;
    var breakers = slo.breakers || {};
    var breakerNames = Object.keys(breakers);
    var openCount = breakerNames.filter(
      function (n) { return breakers[n].state !== "closed"; }).length;
    var tiles = [
      tile("Service", statusSpan(
        health.status === "ok" ? "good" : "warning", health.status)),
      tile("Shed level", health.shed_level
        ? statusSpan("serious", "\\u25cf " + health.shed_level)
        : statusSpan("good", "\\u25cf 0")),
      tile("Queue depth", health.queue_depth,
           qdPoints ? sparkSvg(qdPoints) : ""),
      tile("In flight", (health.inflight || []).length),
      tile("Firing alerts", (slo.alerts || []).length),
      tile("Breakers open", openCount
        ? statusSpan("critical", "\\u25cf " + openCount)
        : statusSpan("good", "\\u25cf 0")),
      tile("Open incidents", incOpen.length
        ? statusSpan(severityClass(incOpen[0].severity),
                     "\\u25cf " + incOpen.length)
        : statusSpan("good", "\\u25cf 0")),
      tile("Degraded served", slo.degraded_total || 0),
      tile("Retries", slo.retries_total || 0),
      tile("Worker restarts", slo.worker_restarts_total || 0),
    ];
    var autoscale = slo.autoscale || {};
    if (autoscale.enabled) {
      tiles.push(tile(
        "Autoscale replicas",
        autoscale.replicas + " / [" + autoscale.min + "\\u2013"
          + autoscale.max + "]"
          + (autoscale.running ? "" : " (stopped)")));
    }
    var tiering = slo.tiering || {};
    if (tiering.enabled) {
      var tc = tiering.state_counts || {};
      tiles.push(tile(
        "Model tiers",
        (tc.active || 0) + " hot / " + (tc.cold || 0) + " cold"
          + (tiering.hbm_budget_bytes
             ? " \\u00b7 " + fmtBytes(tiering.resident_bytes || 0)
               + " of " + fmtBytes(tiering.hbm_budget_bytes)
             : "")
          + (tiering.running ? "" : " (stopped)")));
    }
    var wd = fit.watchdog || null;
    if (wd && wd.checked_unix != null) {
      tiles.push(tile("Fit backend", wd.ok
        ? statusSpan("good", "\\u25cf " + (wd.platform || "ok"))
        : statusSpan("critical", "\\u25cf " + (wd.reason || "degraded"))));
    }
    if ((fit.active || []).length) {
      tiles.push(tile("Active fits", fit.active.length));
    }
    (slo.slos || []).forEach(function (s) {
      tiles.push(tile("Budget left · " + s.name,
                      fmtPct(s.budget_remaining)));
    });
    document.getElementById("tiles").innerHTML = tiles.join("");
    var htiles = historyTiles(hist);
    document.getElementById("history").innerHTML = htiles.length
      ? htiles.join("")
      : '<span class="quiet">no history yet \\u2014 the sampler ' +
        'populates this within a few seconds</span>';
    document.getElementById("slo-rows").innerHTML =
      (slo.slos || []).map(function (s) {
        var st = stateFor(s);
        var b = s.burn_rates || {};
        return "<tr><td class=name>" + s.objective + "</td><td>" +
          s.target + "</td><td>" + fmtBurn(b["5m"]) + "</td><td>" +
          fmtBurn(b["30m"]) + "</td><td>" + fmtBurn(b["1h"]) +
          "</td><td>" + fmtBurn(b["6h"]) + "</td><td>" +
          fmtPct(s.budget_remaining) + "</td><td>" +
          statusSpan(st[0], st[1]) + "</td></tr>";
      }).join("");
    var replicaSets = slo.replicas || {};
    var replicaModels = Object.keys(replicaSets);
    document.getElementById("replicas").innerHTML = replicaModels.length
      ? replicaModels.map(function (m) {
          var doc = replicaSets[m];
          var tiles = (doc.replicas || []).map(function (r) {
            var cls = r.state === "serving" ? "good"
              : (r.state === "draining" ? "warning" : "critical");
            return tile(m + " \\u00b7 " + r.device,
              statusSpan(cls, "\\u25cf " + r.state) +
              '<div class="label" style="margin-top:4px">queue ' +
              r.queue_depth + " \\u00b7 load " + r.load +
              (r.consecutive_failures
                ? " \\u00b7 fails " + r.consecutive_failures : "") +
              "</div>");
          });
          return '<div class="tiles" style="margin-bottom:10px">' +
            tiles.join("") + "</div>";
        }).join("")
      : "no models served yet";
    var fitRuns = (fit.active || []).concat(fit.recent || []);
    document.getElementById("fit").innerHTML = fitRuns.length
      ? "<table><thead><tr><th>Run</th><th>Algo</th><th>Status</th>" +
        "<th>Steps</th><th>Rows/s</th><th>Device s</th><th>MFU</th>" +
        "<th>Stragglers</th></tr></thead><tbody>" +
        fitRuns.map(function (r) {
          var mfu = r.mfu_mean == null ? "\\u2013"
            : (100 * r.mfu_mean).toFixed(1) + "%";
          var strag = (r.stragglers || []).join(" ") || "\\u2013";
          return "<tr><td class=mono>" + r.run_id + "</td>" +
            "<td class=name>" + r.algo + "</td><td>" +
            statusSpan(r.status === "running" ? "warning" : "good",
                       "\\u25cf " + r.status) + "</td><td>" + r.steps +
            (r.steps_failed ? " (" + r.steps_failed + " failed)" : "") +
            "</td><td>" + fmtVal(r.rows_per_sec) + "</td><td>" +
            fmtVal(r.device_seconds) + "</td><td>" + mfu + "</td>" +
            "<td class=name>" + strag + "</td></tr>";
        }).join("") + "</tbody></table>"
      : "no fit runs yet \\u2014 distributed fits and the streaming " +
        "trainer report here";
    document.getElementById("incidents").innerHTML =
      (incOpen.length || incRecent.length)
        ? "<table><thead><tr><th>Detector</th><th>Severity</th>" +
          "<th>Opened</th><th>Duration</th><th>Value vs baseline</th>" +
          "<th>Evidence bundle</th></tr></thead><tbody>" +
          incidentRows(incOpen, "open") +
          incidentRows(incRecent, "resolved") + "</tbody></table>"
        : "no incidents \\u2014 " + (inc.opened_total || 0) +
          " opened / " + (inc.resolved_total || 0) +
          " resolved since start";
    document.getElementById("breakers").innerHTML = breakerNames.length
      ? "<table><thead><tr><th>Model</th><th>State</th>" +
        "<th>Consecutive failures</th><th>Opens</th><th>Open for</th>" +
        "<th>Last error</th></tr></thead><tbody>" +
        breakerNames.map(function (n) {
          var b = breakers[n];
          var cls = b.state === "closed" ? "good"
            : (b.state === "half_open" ? "warning" : "critical");
          return "<tr><td class=name>" + n + "</td><td>" +
            statusSpan(cls, "\\u25cf " + b.state) + "</td><td>" +
            b.consecutive_failures + " / " + b.failure_threshold +
            "</td><td>" + b.opens + "</td><td>" +
            (b.open_for_seconds == null ? "–"
              : b.open_for_seconds.toFixed(1) + " s") +
            "</td><td class=name>" + (b.last_error || "–") +
            "</td></tr>";
        }).join("") + "</tbody></table>"
      : "no models served yet";
    var alerts = slo.alerts || [];
    document.getElementById("alerts").innerHTML = alerts.length
      ? "<table><thead><tr><th>SLO</th><th>Severity</th><th>Short</th>" +
        "<th>Long</th><th>Factor</th></tr></thead><tbody>" +
        alerts.map(function (a) {
          return "<tr><td class=name>" + a.slo + "</td><td>" +
            statusSpan(a.severity === "page_fast" ? "critical" : "serious",
                       a.severity) + "</td><td>" +
            a.short_window + " @ " + fmtBurn(a.short_burn_rate) +
            "</td><td>" + a.long_window + " @ " +
            fmtBurn(a.long_burn_rate) + "</td><td>" + a.factor +
            "</td></tr>";
        }).join("") + "</tbody></table>"
      : "no alerts firing";
    var fleet = {};
    try { fleet = await (await fetch("/debug/fleet")).json(); }
    catch (err) { fleet = {}; }
    var rollup = fleet.rollup || null;
    var fc = (rollup && rollup.forecast) || fleet.forecast || null;
    var fleetTiles = [];
    if (rollup) {
      fleetTiles.push(tile("Hosts up",
        statusSpan(rollup.hosts_up === rollup.hosts_total
                     ? "good" : "critical",
                   "\\u25cf " + rollup.hosts_up + " / " +
                     rollup.hosts_total)));
      (rollup.hosts || []).forEach(function (h) {
        fleetTiles.push(tile(h.host,
          statusSpan(h.up ? "good" : "critical",
                     "\\u25cf " + (h.up ? "up" : "down")) +
          '<div class="label" style="margin-top:4px">' +
          (h.staleness_seconds == null ? "never polled"
            : "stale " + h.staleness_seconds.toFixed(1) + " s") +
          (h.replicas != null ? " \\u00b7 " + h.replicas + " repl"
                              : "") +
          (h.open_incidents ? " \\u00b7 " + h.open_incidents + " inc"
                            : "") + "</div>"));
      });
      var finc = rollup.fleet_incidents || [];
      fleetTiles.push(tile("Fleet incidents", finc.length
        ? statusSpan("critical", "\\u25cf " + finc.length)
        : statusSpan("good", "\\u25cf 0")));
      if (rollup.slo_burn && rollup.slo_burn.max != null) {
        fleetTiles.push(tile("Fleet burn (5m max)",
                             fmtBurn(rollup.slo_burn.max)));
      }
    }
    if (fc && fc.signals) {
      Object.keys(fc.signals).forEach(function (sig) {
        var doc = fc.signals[sig] || {};
        var projections = doc.projections || {};
        var parts = Object.keys(projections).map(function (h) {
          return h + ": " + fmtVal(projections[h]);
        });
        var backtest = (doc.backtest || {});
        fleetTiles.push(tile("forecast \\u00b7 " + sig,
          (parts.join(" \\u00b7 ") || "\\u2013") +
          '<div class="label" style="margin-top:4px">backtest ' +
          (backtest.abs_err_mean == null ? "\\u2013"
            : "|err| " + fmtVal(backtest.abs_err_mean)) + "</div>"));
      });
    }
    document.getElementById("fleet").innerHTML = fleetTiles.length
      ? '<div class="tiles">' + fleetTiles.join("") + "</div>"
      : "not aggregating \\u2014 attach a FleetAggregator " +
        "(obs.federation) to federate peers into this process";
    var tr = await (await fetch("/debug/traces?limit=10")).json();
    var traces = tr.traces || [];
    document.getElementById("traces").innerHTML = traces.length
      ? traces.map(function (t) {
          var root = (t.spans && t.spans[0]) || {};
          return "<details><summary><span class=mono>" + t.trace_id +
            "</span> · " + (root.name || "?") + " · " + t.span_count +
            " spans · " + (root.duration_ms || 0).toFixed(2) +
            " ms</summary><pre>" +
            JSON.stringify(t, null, 1) + "</pre></details>";
        }).join("")
      : "no traces yet";
  } catch (err) {
    document.getElementById("alerts").textContent =
      "refresh failed: " + err;
  }
}
refresh();
setInterval(refresh, 2000);
</script>
</body>
</html>
"""


__all__ = ["DASHBOARD_HTML", "history_document", "make_handler",
           "start_serve_server"]
