"""Uniform per-fit reports: the shared instrumentation entry point.

Every distributed driver is wrapped in ``@fit_instrumentation("<algo>")``
and every user-facing estimator ``fit`` in ``@observed_fit("<algo>")``; both
produce one ``FitReport`` surfaced as ``fit_report_`` on the fitted
model/result (replacing the ad-hoc ``fit_timings_`` dict, which is kept
populated for back-compat), increment the process metrics registry, and —
when ``SPARK_RAPIDS_ML_TPU_TRACE_DIR`` is set — export the fit's span
timeline as Chrome-trace JSON.

The report carries what the ROADMAP's perf work needs per fit: the phase
wall-clock split, rows/bytes processed, the mesh shape and device platform,
the cached ``DeviceHealth`` verdict, and host-side accounting of every
collective the compiled program runs (kind → invocation count + payload
bytes). Collective counts are *program-level* accounting declared by the
drivers (exact for host-looped collectives, schedule×payload for
collectives inside compiled loops) — the XLA-visible truth, not hardware
counters.

Telemetry is never allowed to break a fit: everything outside the wrapped
call itself is exception-guarded.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_ml_tpu.obs import spans
from spark_rapids_ml_tpu.obs.metrics import get_registry
from spark_rapids_ml_tpu.utils.timing import PhaseTimer
from spark_rapids_ml_tpu.utils.tracing import TraceColor

REPORT_ATTR = "fit_report_"


@dataclass
class FitReport:
    """The uniform per-fit observability artifact."""

    algo: str
    trace_id: str
    started_utc: str
    wall_seconds: float
    phases: Dict[str, float] = field(default_factory=dict)
    rows: Optional[int] = None
    features: Optional[int] = None
    bytes_processed: Optional[int] = None
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axes: Optional[Tuple[str, ...]] = None
    device_platform: Optional[str] = None
    device_count: Optional[int] = None
    healthy: Optional[bool] = None
    health: Optional[Dict[str, Any]] = None
    collectives: Dict[str, Dict[str, int]] = field(default_factory=dict)
    n_iter: Optional[int] = None
    # XLA compile attribution (obs.xprof tracked_jit accounting)
    compiles: int = 0
    recompiles: int = 0
    compile_seconds: float = 0.0
    # every executable JAX built during the fit, tracked_jit or not (eager
    # ops): compiled by the backend / fetched from the persistent cache
    # (obs.xprof's jax.monitoring listener)
    programs_compiled: int = 0
    programs_fetched: int = 0
    # Device-memory watermark (obs.memory; host RSS on statless backends)
    peak_device_bytes: Optional[int] = None
    memory: Optional[Dict[str, Any]] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        d = asdict(self)
        if self.mesh_shape is not None:
            d["mesh_shape"] = list(self.mesh_shape)
        if self.mesh_axes is not None:
            d["mesh_axes"] = list(self.mesh_axes)
        return d

    def total_collective_bytes(self) -> int:
        return sum(int(v.get("bytes", 0)) for v in self.collectives.values())

    def total_collective_calls(self) -> int:
        return sum(int(v.get("count", 0)) for v in self.collectives.values())


class FitContext:
    """Mutable accounting for one in-flight fit.

    Obtained inside an instrumented driver via ``current_fit()``; drivers
    record phases (``with ctx.phase("placement"): ...``) and collectives
    (``ctx.record_collective("all_reduce", shape=(n, n), dtype=dt)``).
    """

    __slots__ = (
        "algo", "trace_id", "timer", "collectives", "extra",
        "rows", "features", "bytes_processed", "n_iter", "_lock",
        "compiles", "recompiles", "compile_seconds",
        "programs_built", "programs_fetched",
    )

    def __init__(self, algo: str, trace_id: Optional[str] = None):
        self.algo = algo
        self.trace_id = trace_id or spans.new_trace_id()
        self.timer = PhaseTimer()
        self.collectives: Dict[str, Dict[str, int]] = {}
        self.extra: Dict[str, Any] = {}
        self.rows: Optional[int] = None
        self.features: Optional[int] = None
        self.bytes_processed: Optional[int] = None
        self.n_iter: Optional[int] = None
        self.compiles = 0
        self.recompiles = 0
        self.compile_seconds = 0.0
        self.programs_built = 0
        self.programs_fetched = 0
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a named phase AND emit a nested span for the trace file."""
        with self.timer.phase(name), spans.span(
            f"{self.algo}:{name}", TraceColor.CYAN
        ):
            yield

    def record_compile(self, label: str, seconds: float, *,
                       recompile: bool = False) -> None:
        """Called by ``obs.xprof`` when a tracked function compiles during
        this fit."""
        with self._lock:
            self.compiles += 1
            if recompile:
                self.recompiles += 1
            self.compile_seconds += float(seconds)

    def record_executable(self, fetched: bool) -> None:
        """Called by ``obs.xprof``'s ``jax.monitoring`` listener: JAX built
        an executable during this fit (every build ends in one such call
        with ``fetched=False``; one that came from the persistent cache is
        announced by a call with ``fetched=True`` first)."""
        with self._lock:
            if fetched:
                self.programs_fetched += 1
            else:
                self.programs_built += 1

    def record_collective(
        self,
        kind: str,
        *,
        shape: Optional[Tuple[int, ...]] = None,
        dtype=None,
        nbytes: Optional[int] = None,
        count: int = 1,
    ) -> None:
        """Account ``count`` invocations of a collective, each moving the
        payload described by ``shape``+``dtype`` (or raw ``nbytes``)."""
        if nbytes is None:
            if shape is None:
                nbytes = 0
            else:
                itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
                nbytes = int(np.prod([int(s) for s in shape])) * itemsize
        with self._lock:
            entry = self.collectives.setdefault(
                kind, {"count": 0, "bytes": 0}
            )
            entry["count"] += int(count)
            entry["bytes"] += int(nbytes) * int(count)
        try:
            # mirror into the live fit-path monitor so /debug/fit shows
            # comms accounting while the fit is still running
            from spark_rapids_ml_tpu.obs import fitmon

            fitmon.current_run().record_collective(
                kind, nbytes=int(nbytes), count=int(count)
            )
        except Exception:
            pass

    def set_data(
        self,
        rows: Optional[int] = None,
        features: Optional[int] = None,
        nbytes: Optional[int] = None,
    ) -> None:
        if rows is not None:
            self.rows = int(rows)
        if features is not None:
            self.features = int(features)
        if nbytes is not None:
            self.bytes_processed = int(nbytes)

    def set_iterations(self, n_iter) -> None:
        try:
            self.n_iter = int(n_iter)
        except (TypeError, ValueError):
            pass

    def note(self, **kwargs) -> None:
        self.extra.update(kwargs)


class _NullFitContext(FitContext):
    """No-op context: lets drivers call ``current_fit()`` unconditionally
    even when invoked outside an instrumented entry point."""

    def __init__(self):
        super().__init__("_unobserved")

    @contextlib.contextmanager
    def phase(self, name: str):
        yield

    def record_collective(self, *args, **kwargs) -> None:
        pass

    def record_compile(self, *args, **kwargs) -> None:
        pass

    def record_executable(self, *args, **kwargs) -> None:
        pass

    def set_data(self, *args, **kwargs) -> None:
        pass

    def set_iterations(self, *args) -> None:
        pass

    def note(self, **kwargs) -> None:
        pass


_NULL_CONTEXT = _NullFitContext()
_current_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "sparkml_fit_ctx", default=None
)

_last_reports: Dict[Optional[str], FitReport] = {}
# the one door out for what fits counted: the newest reports of the
# process, oldest first. Reports only — numbers and short strings, never a
# model or an array — and bounded: a window of the benchmark's shortest
# fits holds about fifty.
RECENT_REPORTS = 256
_recent_reports: collections.deque = collections.deque(maxlen=RECENT_REPORTS)
_last_lock = threading.Lock()


def current_fit() -> FitContext:
    """The active fit's context, or a no-op context outside any fit."""
    ctx = _current_ctx.get()
    return ctx if ctx is not None else _NULL_CONTEXT


def recent_fit_reports(n: Optional[int] = None,
                       algo: Optional[str] = None) -> List[FitReport]:
    """The newest ``n`` reports (all the ring holds: at most
    ``RECENT_REPORTS``), optionally of one algo, oldest first — how a
    harness or an operator reads what a run of fits counted
    (``extra["ingest"]``, ``extra["stage"]``, …) without holding on to the
    models."""
    with _last_lock:
        reports = [r for r in _recent_reports
                   if algo is None or r.algo == algo]
    return reports if n is None else reports[max(len(reports) - n, 0):]


def last_fit_report(algo: Optional[str] = None) -> Optional[FitReport]:
    """Most recent report (optionally for one algo) — the escape hatch for
    results the report cannot be attached to."""
    with _last_lock:
        return _last_reports.get(algo)


# -- health / device environment (probed once per process) -----------------

_health_cache: Optional[Dict[str, Any]] = None
_health_lock = threading.Lock()


def _health_once() -> Optional[Dict[str, Any]]:
    global _health_cache
    with _health_lock:
        if _health_cache is None:
            try:
                from spark_rapids_ml_tpu.utils.health import check_devices

                _health_cache = dict(check_devices().__dict__)
            except Exception:
                _health_cache = {}
        return _health_cache or None


# -- report assembly -------------------------------------------------------


_utcnow = spans.utcnow_iso


def _find_mesh(args, kwargs):
    try:
        from jax.sharding import Mesh
    except Exception:
        return None
    mesh = kwargs.get("mesh")
    if isinstance(mesh, Mesh):
        return mesh
    for a in args:
        if isinstance(a, Mesh):
            return a
    return None


def _array_stats(value):
    """(rows, features, nbytes) for an array-like, else None."""
    shape = getattr(value, "shape", None)
    if not shape or not isinstance(shape, tuple):
        return None
    try:
        rows = int(shape[0])
        features = int(shape[1]) if len(shape) > 1 else None
        nbytes = getattr(value, "nbytes", None)
        if nbytes is None:
            itemsize = getattr(
                getattr(value, "dtype", None), "itemsize", 8
            )
            nbytes = int(np.prod([int(s) for s in shape])) * itemsize
        return rows, features, int(nbytes)
    except (TypeError, ValueError):
        return None


def _infer_data_stats(ctx: FitContext, args, kwargs) -> None:
    """Fill rows/features/bytes from the call's array arguments unless the
    driver already set them explicitly."""
    if ctx.rows is not None and ctx.bytes_processed is not None:
        return
    total_bytes = 0
    first = None
    flat = []
    for a in list(args) + list(kwargs.values()):
        if isinstance(a, tuple):
            flat.extend(a)
        else:
            flat.append(a)
    for a in flat:
        stats = _array_stats(a)
        if stats is None:
            continue
        if first is None:
            first = stats
        total_bytes += stats[2]
    if first is not None:
        if ctx.rows is None:
            ctx.rows = first[0]
        if ctx.features is None:
            ctx.features = first[1]
    if ctx.bytes_processed is None and total_bytes:
        ctx.bytes_processed = total_bytes


def _mesh_fields(mesh) -> Dict[str, Any]:
    if mesh is None:
        return {}
    try:
        from spark_rapids_ml_tpu.parallel.mesh import mesh_shape

        summary = mesh_shape(mesh)
        return {
            "mesh_shape": summary["shape"],
            "mesh_axes": summary["axes"],
            "device_platform": summary["platform"],
            "device_count": summary["devices"],
        }
    except Exception:
        return {}


def _memory_fields() -> Dict[str, Any]:
    """End-of-fit device-memory watermark (PJRT peak, host RSS fallback)."""
    try:
        from spark_rapids_ml_tpu.obs.memory import (
            memory_watermarks,
            record_memory_metrics,
        )

        wm = memory_watermarks()
        record_memory_metrics(wm)
        return {"peak_device_bytes": wm.get("peak_bytes"), "memory": wm}
    except Exception:
        return {}


def _build_report(
    ctx: FitContext, started: str, wall: float, mesh
) -> FitReport:
    phases = ctx.timer.as_dict()
    phases.setdefault("total", wall)
    health = _health_once()
    fields: Dict[str, Any] = _mesh_fields(mesh)
    if health:
        fields.setdefault("device_platform", health.get("platform"))
        fields.setdefault("device_count", health.get("device_count"))
    fields.update(_memory_fields())
    return FitReport(
        algo=ctx.algo,
        trace_id=ctx.trace_id,
        started_utc=started,
        wall_seconds=wall,
        phases=phases,
        rows=ctx.rows,
        features=ctx.features,
        bytes_processed=ctx.bytes_processed,
        healthy=health.get("healthy") if health else None,
        health=health,
        collectives={k: dict(v) for k, v in ctx.collectives.items()},
        n_iter=ctx.n_iter,
        compiles=ctx.compiles,
        recompiles=ctx.recompiles,
        compile_seconds=ctx.compile_seconds,
        programs_compiled=ctx.programs_built - ctx.programs_fetched,
        programs_fetched=ctx.programs_fetched,
        extra=dict(ctx.extra),
        **fields,
    )


def _flight_deadline(algo: str, trace_id: str):
    """The watchdog context for one fit (no-op if flight is unavailable)."""
    try:
        from spark_rapids_ml_tpu.obs import flight

        return flight.deadline(f"fit:{algo}", trace_id=trace_id)
    except Exception:
        return contextlib.nullcontext()


def _fitmon_run(algo: str, trace_id: str):
    """The fit-path step monitor's run context (obs/fitmon.py): every
    instrumented driver is a monitored FitRun, so its steps land in
    ``/debug/fit`` and the ``sparkml_fit_*`` history. No-op when fitmon
    is disabled or unavailable."""
    try:
        from spark_rapids_ml_tpu.obs import fitmon

        return fitmon.fit_run(algo, trace_id=trace_id)
    except Exception:
        return contextlib.nullcontext()


def _record_metrics(report: FitReport) -> None:
    reg = get_registry()
    algo = report.algo
    reg.counter(
        "sparkml_fits_total", "completed fits", ("algo",)
    ).inc(algo=algo)
    if report.compiles:
        reg.counter(
            "sparkml_fit_compiles_total",
            "XLA compilations attributed to fits", ("algo",),
        ).inc(report.compiles, algo=algo)
    if report.recompiles:
        reg.counter(
            "sparkml_fit_recompiles_total",
            "XLA re-compilations attributed to fits", ("algo",),
        ).inc(report.recompiles, algo=algo)
    reg.histogram(
        "sparkml_fit_seconds", "fit wall-clock seconds", ("algo",)
    ).observe(report.wall_seconds, algo=algo)
    if report.rows:
        reg.counter(
            "sparkml_rows_processed_total", "rows seen by fits", ("algo",)
        ).inc(report.rows, algo=algo)
    if report.bytes_processed:
        reg.counter(
            "sparkml_bytes_processed_total", "input bytes seen by fits",
            ("algo",),
        ).inc(report.bytes_processed, algo=algo)
    for kind, entry in report.collectives.items():
        reg.counter(
            "sparkml_collective_calls_total",
            "collective invocations (program-level accounting)",
            ("algo", "kind"),
        ).inc(entry.get("count", 0), algo=algo, kind=kind)
        reg.counter(
            "sparkml_collective_bytes_total",
            "collective payload bytes (program-level accounting)",
            ("algo", "kind"),
        ).inc(entry.get("bytes", 0), algo=algo, kind=kind)
    if report.device_platform:
        reg.gauge(
            "sparkml_device_count", "visible devices", ("platform",)
        ).set(report.device_count or 0, platform=report.device_platform)
    ingest = report.extra.get("ingest")
    if isinstance(ingest, dict):
        _record_ingest_metrics(reg, algo, ingest)


def _record_ingest_metrics(reg, algo: str, ingest: Dict[str, Any]) -> None:
    """A streamed fit's ingest counters (``ops.streaming.IngestTrace``)
    as process counters: what crossed to the chips, what the main thread
    waited for the put window, how long each chip had a put outstanding,
    and whether the keep and the staging buffers engaged."""
    for name, key, text in (
        ("sparkml_ingest_bytes_put_total", "bytes_put",
         "bytes handed to device_put by streamed fits"),
        ("sparkml_ingest_put_wait_seconds_total", "put_wait_seconds",
         "seconds the main thread waited for a chip's put window"),
        ("sparkml_ingest_batches_kept_total", "batches_kept",
         "device batches of pass 1 kept for pass 2"),
        ("sparkml_ingest_bytes_reblocked_total", "bytes_reblocked",
         "bytes copied on the host to assemble device batches"),
    ):
        reg.counter(name, text, ("algo",)).inc(ingest.get(key, 0), algo=algo)
    for chip in ingest.get("per_chip", ()):
        reg.counter(
            "sparkml_ingest_crossing_seconds_total",
            "seconds during which a put of the chip was outstanding",
            ("algo", "chip"),
        ).inc(chip.get("crossing_seconds", 0.0), algo=algo,
              chip=str(chip.get("device")))
    for outcome in ("reused", "fresh"):
        reg.counter(
            "sparkml_ingest_staging_total",
            "copied batches by where their staging buffer came from",
            ("algo", "outcome"),
        ).inc(ingest.get("staging_" + outcome, 0), algo=algo,
              outcome=outcome)


def _publish(report: FitReport) -> None:
    with _last_lock:
        _last_reports[report.algo] = report
        _last_reports[None] = report
        _recent_reports.append(report)
    _record_metrics(report)
    spans.maybe_export_trace(report.trace_id, report.algo)


# -- result attachment -----------------------------------------------------

_subclass_cache: Dict[type, type] = {}
_subclass_lock = threading.Lock()


def _reporting_subclass(cls: type) -> type:
    """A cached subclass of ``cls`` that accepts instance attributes.

    NamedTuple/tuple results have ``__slots__ = ()`` and refuse attributes;
    a trivial subclass (same name, no slots) behaves identically —
    unpacking, ``_fields``, isinstance — but carries ``fit_report_``.
    """
    with _subclass_lock:
        sub = _subclass_cache.get(cls)
        if sub is None:
            sub = type(cls.__name__, (cls,), {"__obs_reported__": True})
            _subclass_cache[cls] = sub
        return sub


def attach_report(result, report, attr: str = REPORT_ATTR):
    """Attach a report to a result under ``attr``, wrapping when needed.

    Handles model objects (plain setattr), NamedTuples and tuples
    (attribute-capable subclass), and ndarrays (subclass view). Results
    that cannot carry attributes are returned unchanged — the report stays
    reachable via ``last_fit_report()`` / ``last_transform_report()``.
    """
    try:
        setattr(result, attr, report)
        return result
    except (AttributeError, TypeError):
        pass
    try:
        if isinstance(result, np.ndarray):
            out = result.view(_reporting_subclass(type(result)))
            setattr(out, attr, report)
            return out
        if isinstance(result, tuple):
            cls = type(result)
            sub = _reporting_subclass(cls)
            if hasattr(cls, "_make"):  # NamedTuple
                out = sub._make(result)
            else:
                out = tuple.__new__(sub, result)
            setattr(out, attr, report)
            return out
    except Exception:
        pass
    return result


# -- the two decorators ----------------------------------------------------


def fit_instrumentation(algo: str, attach: bool = True):
    """Wrap a distributed driver: fit context + root span + report.

    The decorated function's result gains ``fit_report_`` (wrapped into an
    attribute-capable subclass when needed). ``scripts/
    check_instrumentation.py`` statically enforces that every
    ``parallel/distributed_*`` entry point carries this decorator.
    """

    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            ctx = FitContext(algo, trace_id=spans.current_trace_id())
            token = _current_ctx.set(ctx)
            started = _utcnow()
            t0 = time.perf_counter()
            fitmon_run = None
            try:
                with _flight_deadline(algo, ctx.trace_id), _fitmon_run(
                    algo, ctx.trace_id
                ) as fitmon_run, spans.span(
                    f"fit:{algo}", TraceColor.GREEN, trace_id=ctx.trace_id
                ), ctx.timer.phase("total"):
                    result = fn(*args, **kwargs)
            finally:
                _current_ctx.reset(token)
            wall = time.perf_counter() - t0
            try:
                _infer_data_stats(ctx, args, kwargs)
                report = _build_report(
                    ctx, started, wall, _find_mesh(args, kwargs)
                )
                _publish(report)
                if fitmon_run is not None and getattr(
                    fitmon_run, "run_id", None
                ):
                    # join the finished run to its uniform report so
                    # /debug/fit shows the same rollup the model carries
                    fitmon_run.report = {
                        "wall_seconds": report.wall_seconds,
                        "rows": report.rows,
                        "n_iter": report.n_iter,
                        "collective_bytes":
                            report.total_collective_bytes(),
                    }
                if attach:
                    result = attach_report(result, report)
            except Exception:
                pass  # telemetry must never break a fit
            return result

        wrapper.__obs_instrumented__ = algo
        return wrapper

    return decorator


def observed_fit(algo: str):
    """Wrap an estimator ``fit`` method: the fitted model gains a uniform
    ``fit_report_`` (phases merged from the model's ``fit_timings_``, which
    stays populated for back-compat)."""

    def decorator(method):
        @functools.wraps(method)
        def wrapper(self, dataset, *args, **kwargs):
            ctx = FitContext(algo, trace_id=spans.current_trace_id())
            token = _current_ctx.set(ctx)
            started = _utcnow()
            t0 = time.perf_counter()
            try:
                with _flight_deadline(algo, ctx.trace_id), spans.span(
                    f"fit:{algo}", TraceColor.GREEN, trace_id=ctx.trace_id
                ):
                    model = method(self, dataset, *args, **kwargs)
            finally:
                _current_ctx.reset(token)
            wall = time.perf_counter() - t0
            try:
                stats = _array_stats(dataset)
                if stats is not None:
                    ctx.set_data(
                        rows=stats[0], features=stats[1], nbytes=stats[2]
                    )
                for name, seconds in (
                    getattr(model, "fit_timings_", None) or {}
                ).items():
                    ctx.timer.add(name, seconds)
                report = _build_report(ctx, started, wall, None)
                _publish(report)
                try:
                    setattr(model, REPORT_ATTR, report)
                except (AttributeError, TypeError):
                    pass
            except Exception:
                pass  # telemetry must never break a fit
            return model

        wrapper.__obs_instrumented__ = algo
        return wrapper

    return decorator


def observed_transform(algo=None):
    """Moved: the serving-tier decorator lives in ``obs.serving`` (full
    ``TransformReport`` + sketch latency + numerics sentinel). This alias
    keeps old import paths working."""
    from spark_rapids_ml_tpu.obs.serving import observed_transform as _ot

    return _ot(algo)
