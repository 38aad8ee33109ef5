"""Unified telemetry: metrics registry, trace spans, per-fit reports.

One import surface for everything observability:

* ``get_registry()`` — the process-wide metrics registry (counters,
  gauges, histograms with labels; ``.snapshot()`` for JSON,
  ``.prometheus_text()`` / ``start_prometheus_server()`` for scraping);
* ``span(...)`` / ``get_recorder()`` — structured nested trace spans in a
  ring buffer, exportable as Chrome-trace/Perfetto JSON (env-gated on
  ``SPARK_RAPIDS_ML_TPU_TRACE_DIR``);
* ``fit_instrumentation`` / ``observed_fit`` / ``current_fit`` — the
  shared instrumentation entry points that give every distributed driver
  and estimator a uniform ``fit_report_``;
* ``observed_transform`` / ``current_transform`` / ``transform_phase`` —
  the serving tier (``obs.serving``): every transform/predict entry point
  yields a ``TransformReport``, feeds the latency quantile sketch
  (``obs.quantiles``), and passes the numerics sentinel;
* back-compat re-exports of the underlying ``utils`` primitives
  (``TraceRange``, ``PhaseTimer``, ``DeviceHealth``…), so telemetry
  consumers need only this package.
"""

from spark_rapids_ml_tpu.obs.metrics import (  # noqa: F401
    Counter,
    DEFAULT_BUCKETS,
    Gauge,
    Histogram,
    MetricsRegistry,
    Summary,
    get_registry,
    start_prometheus_server,
)
from spark_rapids_ml_tpu.obs.quantiles import (  # noqa: F401
    QuantileSketch,
    merge_all,
)
from spark_rapids_ml_tpu.obs.spans import (  # noqa: F401
    SpanEvent,
    SpanRecorder,
    TRACE_DIR_ENV,
    active_spans,
    assemble_trace,
    current_span_id,
    current_trace_id,
    get_recorder,
    maybe_export_trace,
    new_trace_id,
    recent_traces,
    record_event,
    span,
)
from spark_rapids_ml_tpu.obs.tracectx import (  # noqa: F401
    TRACEPARENT_HEADER,
    TraceContext,
    activate,
    capture,
    current_context,
    ensure_context,
    inflight_request,
    inflight_requests,
    new_context,
    new_span_id,
    parse_traceparent,
    traced_thread,
)
from spark_rapids_ml_tpu.obs.slo import (  # noqa: F401
    BURN_POLICIES,
    SLO,
    SloSet,
    WindowedCounts,
    default_slos,
)
from spark_rapids_ml_tpu.obs.xprof import (  # noqa: F401
    CompileEvent,
    STORM_ENV,
    TrackedJit,
    analytic_mfu,
    clear_all_signature_caches,
    compile_log,
    compile_stats,
    peak_flops_per_second,
    reset_compile_log,
    signature_count,
    track_compiles,
    tracked_jit,
)
from spark_rapids_ml_tpu.obs.aotcache import (  # noqa: F401
    ExecutableCache,
    configure_executable_cache,
    get_executable_cache,
)
from spark_rapids_ml_tpu.obs.memory import (  # noqa: F401
    device_memory_stats,
    host_peak_rss_bytes,
    memory_watermarks,
    peak_bytes_in_use,
    record_memory_metrics,
)
from spark_rapids_ml_tpu.obs.flight import (  # noqa: F401
    DUMP_DIR_ENV,
    FIT_BUDGET_ENV,
    TRANSFORM_BUDGET_ENV,
    Watchdog,
    build_dump,
    deadline,
    dump,
    dump_dir,
    get_watchdog,
)
from spark_rapids_ml_tpu.obs import flight  # noqa: F401
from spark_rapids_ml_tpu.obs.logging import (  # noqa: F401
    StructuredLogger,
    get_logger,
)
from spark_rapids_ml_tpu.obs.robust import (  # noqa: F401
    mad,
    noise_band,
    robust_zscore,
)
from spark_rapids_ml_tpu.obs.anomaly import (  # noqa: F401
    Detector,
    Finding,
    MadSpikeDetector,
    RateOfChangeDetector,
    ThresholdDetector,
    builtin_detectors,
)
from spark_rapids_ml_tpu.obs.incidents import (  # noqa: F401
    Incident,
    IncidentEngine,
    IncidentManager,
    get_incident_engine,
    reset_incident_engine,
)
from spark_rapids_ml_tpu.obs import retention  # noqa: F401
from spark_rapids_ml_tpu.obs.tsdb import (  # noqa: F401
    MetricsSampler,
    TimeSeriesStore,
    get_sampler,
    get_tsdb,
    start_sampling,
    stop_sampling,
)
from spark_rapids_ml_tpu.obs.devmon import (  # noqa: F401
    DeviceMonitor,
    get_device_monitor,
)
from spark_rapids_ml_tpu.obs import profiler  # noqa: F401
from spark_rapids_ml_tpu.obs.fitmon import (  # noqa: F401
    BackendWatchdog,
    FitMonitor,
    FitRun,
    StepMonitor,
    current_run,
    debug_fit_doc,
    detect_stragglers,
    device_peaks,
    fit_report,
    fit_run,
    get_fit_monitor,
    reset_fitmon,
    roofline_bound,
    step_mfu,
)
from spark_rapids_ml_tpu.obs.report import (  # noqa: F401
    FitContext,
    FitReport,
    REPORT_ATTR,
    attach_report,
    current_fit,
    fit_instrumentation,
    last_fit_report,
    observed_fit,
    recent_fit_reports,
)
from spark_rapids_ml_tpu.obs.serving import (  # noqa: F401
    NUMERICS_SAMPLE_ENV,
    TRANSFORM_REPORT_ATTR,
    TransformContext,
    TransformReport,
    check_output_numerics,
    current_transform,
    last_transform_report,
    latency_quantiles,
    observed_transform,
    transform_phase,
)

# Back-compat shims: the pre-obs utils primitives, re-exported so telemetry
# call sites can import everything from one place (utils.* keeps working).
from spark_rapids_ml_tpu.utils.tracing import (  # noqa: F401
    TraceColor,
    TraceRange,
)
from spark_rapids_ml_tpu.utils.timing import PhaseTimer  # noqa: F401
from spark_rapids_ml_tpu.utils.health import (  # noqa: F401
    DeviceHealth,
    check_devices,
    check_devices_subprocess,
)

__all__ = [
    "BURN_POLICIES",
    "CompileEvent",
    "Counter",
    "DEFAULT_BUCKETS",
    "DUMP_DIR_ENV",
    "BackendWatchdog",
    "Detector",
    "DeviceHealth",
    "DeviceMonitor",
    "FIT_BUDGET_ENV",
    "Finding",
    "FitContext",
    "FitMonitor",
    "FitReport",
    "FitRun",
    "StepMonitor",
    "Gauge",
    "Histogram",
    "Incident",
    "IncidentEngine",
    "IncidentManager",
    "MadSpikeDetector",
    "RateOfChangeDetector",
    "ThresholdDetector",
    "MetricsRegistry",
    "MetricsSampler",
    "NUMERICS_SAMPLE_ENV",
    "PhaseTimer",
    "QuantileSketch",
    "REPORT_ATTR",
    "SLO",
    "STORM_ENV",
    "SloSet",
    "SpanEvent",
    "SpanRecorder",
    "StructuredLogger",
    "Summary",
    "TRACEPARENT_HEADER",
    "TRACE_DIR_ENV",
    "TRANSFORM_BUDGET_ENV",
    "TRANSFORM_REPORT_ATTR",
    "TimeSeriesStore",
    "TraceColor",
    "TraceContext",
    "TraceRange",
    "TrackedJit",
    "TransformContext",
    "TransformReport",
    "Watchdog",
    "activate",
    "active_spans",
    "analytic_mfu",
    "assemble_trace",
    "attach_report",
    "build_dump",
    "builtin_detectors",
    "capture",
    "check_devices",
    "check_devices_subprocess",
    "check_output_numerics",
    "compile_log",
    "clear_all_signature_caches",
    "compile_stats",
    "configure_executable_cache",
    "ExecutableCache",
    "get_executable_cache",
    "signature_count",
    "current_context",
    "current_fit",
    "current_run",
    "current_span_id",
    "current_trace_id",
    "current_transform",
    "deadline",
    "debug_fit_doc",
    "default_slos",
    "detect_stragglers",
    "device_memory_stats",
    "device_peaks",
    "dump",
    "dump_dir",
    "ensure_context",
    "fit_instrumentation",
    "fit_report",
    "fit_run",
    "flight",
    "get_device_monitor",
    "get_fit_monitor",
    "get_incident_engine",
    "get_logger",
    "get_recorder",
    "get_registry",
    "get_sampler",
    "get_tsdb",
    "get_watchdog",
    "host_peak_rss_bytes",
    "mad",
    "inflight_request",
    "inflight_requests",
    "last_fit_report",
    "last_transform_report",
    "latency_quantiles",
    "maybe_export_trace",
    "memory_watermarks",
    "merge_all",
    "new_context",
    "new_span_id",
    "new_trace_id",
    "noise_band",
    "observed_fit",
    "observed_transform",
    "parse_traceparent",
    "peak_bytes_in_use",
    "peak_flops_per_second",
    "profiler",
    "recent_fit_reports",
    "recent_traces",
    "record_event",
    "record_memory_metrics",
    "reset_compile_log",
    "reset_fitmon",
    "reset_incident_engine",
    "retention",
    "robust_zscore",
    "roofline_bound",
    "step_mfu",
    "span",
    "start_prometheus_server",
    "start_sampling",
    "stop_sampling",
    "traced_thread",
    "track_compiles",
    "tracked_jit",
    "transform_phase",
    "WindowedCounts",
]
