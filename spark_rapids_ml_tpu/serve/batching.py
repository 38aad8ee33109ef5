"""Shape-bucketed dynamic micro-batching with a pipelined inner loop.

Requests enqueue; one worker per batcher coalesces them — up to
``max_batch_rows`` rows or ``max_wait_ms`` of linger, whichever lands
first — writes their row matrices into a reusable per-bucket staging
array (``utils.padding.StagingPool``), runs ONE model call over it, and
splits the result back per request in enqueue order. Steady-state
traffic therefore executes a handful of compiled XLA signatures (one per
bucket) no matter how ragged the request sizes are — the fixed-shape
funnel of PAPERS.md's Flare / TPU-linear-algebra lineage.

The hot path is a **two-stage pipeline** (the PR 9 latency war). The
pre-pipeline loop ran submit → f64 concat → pad → one BLOCKING transform
→ host sync → split, serially, with the device idle during both host
phases. Now each batch travels three steps the worker interleaves
across batches:

* **stage**   — pad batch N+1 into a rotating pinned staging array (in
  the model's transform dtype — no blanket f64 copy) and start its
  host→device transfer (``jax.device_put`` via the model's
  ``ServingProgram.put``) while batch N computes;
* **dispatch** — launch the compiled transform via JAX **async
  dispatch** (``ServingProgram.run``) without forcing a sync; the
  serving kernels donate the staged input buffer (``donate_argnums``),
  which is safe because a retry always re-stages from host rows;
* **complete** — the ``np.asarray`` host sync lives ONLY here
  (``_complete_batch`` — rule 9 of ``scripts/check_instrumentation.py``
  statically rejects host syncs anywhere else in this worker loop): the
  oldest entry of a bounded in-flight window (depth
  ``SPARK_RAPIDS_ML_TPU_SERVE_PIPELINE_DEPTH``, default 2) is drained,
  padding sliced off, the output check run, and rows split to requests.

So compute of batch N+1 overlaps both the transfer of N+2 and the
result fetch of N. Models that expose no device-resident
``serving_transform_program`` (``obs.serving.ServingProgram``) keep the
exact pre-pipeline blocking path (window depth 1, f64 staging) — f32/f64
outputs through the pipeline are bit-equal to that path because the
dispatched program is the same XLA module.

Correctness invariants (tested in ``tests/test_serve_batching.py`` and
``tests/test_serve_pipeline.py``):

* padded rows are masked out before the split — they never appear in any
  response, at any pipeline depth;
* each request gets exactly its own rows back, in its own order, however
  the coalescer grouped them;
* a request whose deadline expired while queued is shed with
  ``DeadlineExpired`` *before* touching the device, and its neighbours
  still get their own rows;
* a batch-level failure propagates the SAME exception to every request in
  that batch — and ONLY that batch: the other entries of the in-flight
  window complete normally;
* a donated staged buffer is never one a retry still holds — the engine's
  retry path re-enters ``submit`` with the caller's host rows and stages a
  fresh buffer.

Every stage emits through ``obs``: queue-depth / batch-occupancy /
padding-waste gauges, per-stage latency (queue wait, stage, dispatch,
sync, and the combined execute) into the ``Summary`` quantile sketches,
shed/rejection counters, plus the pipeline posture itself —
``sparkml_serve_device_busy_seconds_total`` (union time with >= 1 batch
in flight; the bench's ``pipeline_overlap_fraction`` numerator),
``sparkml_serve_pipeline_overlap_seconds_total`` (time with >= 2 in
flight) and the ``sparkml_serve_pipeline_inflight`` gauge — all sampled
into the TSDB for the dashboard. Async batches publish a per-batch
``TransformReport`` with the stage/dispatch/sync phase split
(``obs.serving.PipelineTransform``) since they run around the models'
decorated entry points.

Tracing: each request enqueues with its captured ``TraceContext``
(``obs.tracectx``); the worker files a queue-wait span into the request's
trace at pop time, runs the dispatch under a **fan-in batch span** whose
``links`` carry every member request's trace id (the Dapper fan-in edge —
``assemble_trace`` grafts the batch subtree into each member's tree),
files the completion-side sync interval as a ``serve:sync`` child event,
and resolves every response latch with the member's context re-activated.
Rule 5 of ``scripts/check_instrumentation.py`` statically enforces this
capture/activate contract on every handoff in ``serve/``.

Worker supervision (a device backend hang must not take the whole batcher
down with it):

* a worker that **crashes** (an exception escaping the batch path — the
  fault plane's ``crash_worker`` injects exactly this) has every batch in
  its in-flight window failed fast with ``WorkerCrashed`` and is
  **restarted** by its supervisor (``sparkml_serve_worker_restarts_total``);
  once the restart budget (``max_restarts``) is exhausted the batcher is
  marked dead and every queued + future request fails fast instead of
  hanging to its deadline;
* a worker that **wedges** (one batch exceeding ``worker_budget_s``
  between dispatch and completion — the ``obs.flight`` watchdog budget,
  armed per in-flight batch) is detected by the armed deadline whose
  ``on_expire`` hook fails the ENTIRE in-flight window fast (the stuck
  thread is the only one that could have drained it), abandons the stuck
  thread (generation-guarded: its late results can never resolve
  already-failed latches), spawns a replacement worker with a fresh
  staging pool, and still produces the usual ``budget_exceeded`` flight
  dump — no stuck in-flight window survives a restart;
* ``close()`` ends with a final sweep: whatever the worker did not
  serve (it crashed, wedged, or the join timed out) is failed — every
  request gets exactly one terminal outcome, never a silent hang.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_ml_tpu.obs import accounting
from spark_rapids_ml_tpu.obs import flight, get_registry, span, tracectx
from spark_rapids_ml_tpu.obs import serving as obs_serving
from spark_rapids_ml_tpu.obs import spans as spans_mod
from spark_rapids_ml_tpu.obs.devmon import get_device_monitor
from spark_rapids_ml_tpu.serve.admission import (
    INTERACTIVE,
    ShedLoad,
    retry_after_cap,
)
from spark_rapids_ml_tpu.serve.faults import (
    InjectedWorkerCrash,
    fault_plane,
)
from spark_rapids_ml_tpu.serve.scheduler import FifoQueue
from spark_rapids_ml_tpu.utils.padding import (
    StagingPool,
    bucket_for,
    default_buckets,
    pad_to_bucket,
    padding_waste,
)

PIPELINE_DEPTH_ENV = "SPARK_RAPIDS_ML_TPU_SERVE_PIPELINE_DEPTH"


def pipeline_depth_from_env(default: int = 2) -> int:
    """The in-flight window depth for async-capable models (>= 1; 1
    restores the fully synchronous pre-pipeline loop)."""
    try:
        return max(int(os.environ.get(PIPELINE_DEPTH_ENV, default)), 1)
    except ValueError:
        return default


class QueueFull(RuntimeError):
    """Admission control: the bounded request queue is at
    ``max_queue_depth`` — shed load at the door instead of building an
    unbounded latency backlog."""


class DeadlineExpired(RuntimeError):
    """The request's deadline passed before (or while) it could be
    served; it was shed without spending device time."""


class BatcherClosed(RuntimeError):
    """The batcher is draining/closed and accepts no new requests."""


class WaitTimeout(TimeoutError):
    """The caller's ``wait`` timeout elapsed before the batcher resolved
    the request. Congestion, not a device verdict: the engine neither
    retries it (the original request is still queued — a re-submit would
    duplicate device work and multiply the caller's timeout) nor feeds
    it to the breaker."""


class WorkerCrashed(RuntimeError):
    """The batcher's worker thread died or wedged past its watchdog
    budget; the request is failed FAST (distinct from ``DeadlineExpired``
    — the service broke, the client did nothing wrong) and counted in
    ``sparkml_serve_errors_total{error="worker_crashed"}``. Retryable:
    a supervised restart usually restores service immediately."""


class AsyncTransformSpec:
    """The engine-built async serving contract for one model — the three
    pipeline steps the worker interleaves, plus the staging dtype.

    ``stage(staged_host) → device_handle`` starts the host→device
    transfer; ``dispatch(device_handle) → opaque`` launches the transform
    via async dispatch (synchronous raises here fail only that batch);
    ``complete(opaque) → array`` is the host sync, called only from the
    batcher's designated completion step. ``dtype`` is what ``submit``
    coerces request rows to (the model's transform dtype); ``algo`` /
    ``precision`` label the per-batch ``TransformReport``.
    """

    __slots__ = ("stage", "dispatch", "complete", "dtype", "algo",
                 "precision", "program")

    def __init__(self, stage: Callable, dispatch: Callable,
                 complete: Callable, dtype, algo: str,
                 precision: str = "native", program=None):
        self.stage = stage
        self.dispatch = dispatch
        self.complete = complete
        self.dtype = np.dtype(dtype)
        self.algo = algo
        self.precision = precision
        # the raw (fault-plane-free) ServingProgram, kept reachable for
        # engine warmup so precompiling the ladder never eats armed faults
        self.program = program


class _Request:
    """One enqueued predict request; a latch the caller waits on.

    ``trace_ctx`` is the submitter's captured ``TraceContext`` — the
    worker re-activates it around every resolution (result, shed, batch
    failure) and files the queue-wait span into its trace.

    ``tenant`` / ``priority`` / ``over_quota`` are the admission
    controller's verdict (``serve.admission``) — what the weighted-fair
    queue (``serve.scheduler``) schedules and the preemption path ranks
    by."""

    __slots__ = ("rows", "n", "enqueued", "enqueued_perf", "deadline",
                 "trace_ctx", "tenant", "priority", "over_quota",
                 "_event", "result", "error")

    def __init__(self, rows: np.ndarray, deadline: Optional[float],
                 trace_ctx: Optional[tracectx.TraceContext] = None,
                 tenant: str = "default", priority: str = INTERACTIVE,
                 over_quota: bool = False):
        self.rows = rows
        self.n = int(rows.shape[0])
        self.enqueued = time.monotonic()
        self.enqueued_perf = time.perf_counter()  # spans' timeline clock
        self.deadline = deadline
        self.trace_ctx = trace_ctx
        self.tenant = tenant
        self.priority = priority
        self.over_quota = over_quota
        self._event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now or time.monotonic()) >= self.deadline)

    def set_result(self, value: np.ndarray) -> bool:
        """First writer wins: a wedged worker's LATE result must never
        overwrite the ``WorkerCrashed`` the watchdog already delivered
        (exactly one terminal outcome per request)."""
        if self._event.is_set():
            return False
        self.result = value
        self._event.set()
        return True

    def set_error(self, exc: BaseException) -> bool:
        if self._event.is_set():
            return False
        self.error = exc
        self._event.set()
        return True

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until served; raises the request's error if it was shed
        or its batch failed."""
        if not self._event.wait(timeout):
            raise WaitTimeout("request not served within wait timeout")
        if self.error is not None:
            raise self.error
        return self.result


class _InFlight:
    """One dispatched batch traveling the stage → dispatch → complete
    pipeline; the supervision unit crash/wedge handlers fail."""

    __slots__ = ("batch", "ctx", "member_ids", "handle", "n", "bucket",
                 "features", "bytes_in", "watchdog", "dispatched",
                 "stage_seconds", "dispatch_seconds", "sync_seconds",
                 "report", "batch_span_id")

    def __init__(self, batch: List[_Request],
                 ctx: tracectx.TraceContext,
                 member_ids: Tuple[str, ...] = ()):
        self.batch = batch
        self.ctx = ctx
        self.member_ids = member_ids
        self.handle: Any = None
        self.n = 0
        self.bucket = 0
        self.features: Optional[int] = None
        self.bytes_in: Optional[int] = None
        self.watchdog: Optional[int] = None
        self.dispatched = False
        self.stage_seconds = 0.0
        self.dispatch_seconds = 0.0
        self.sync_seconds = 0.0
        self.report: Optional[obs_serving.PipelineTransform] = None
        self.batch_span_id: Optional[str] = None


def _identity(value):
    return value


class MicroBatcher:
    """One model's request queue + pipelined coalescing worker.

    ``transform_fn`` receives the staged (bucket, d) matrix and must
    return a row-aligned array-like (bucket rows, or at least the real
    rows) — the batcher slices off padding and splits per request. It is
    the BLOCKING path, used when no ``async_spec`` is given (window depth
    is then pinned at 1, preserving the pre-pipeline behavior exactly).

    ``async_spec`` (an ``AsyncTransformSpec``) replaces it with the
    stage/dispatch/complete pipeline steps; ``pipeline_depth`` bounds the
    in-flight window (None → ``SPARK_RAPIDS_ML_TPU_SERVE_PIPELINE_DEPTH``,
    default 2).

    ``dtype`` is what ``submit`` coerces request rows to — the model's
    transform dtype, so a caller already sending matching rows pays zero
    copies at the door (the old unconditional float64 coercion doubled
    copy bytes for f32 models).

    ``output_check`` (optional) runs over the REAL rows only — after the
    padding slice, before the per-request split. Zero-padding rows can
    legitimately map to NaN/Inf under log/reciprocal kernels, so a guard
    that scanned the padded output would poison healthy batches; this
    hook sees exactly what callers will receive. A raise here fails the
    whole batch (same propagation as a transform failure).
    """

    def __init__(
        self,
        transform_fn: Callable[[np.ndarray], Any],
        *,
        name: str = "model",
        max_batch_rows: int = 1024,
        max_wait_ms: float = 5.0,
        max_queue_depth: int = 256,
        buckets: Optional[Sequence[int]] = None,
        worker_budget_s: Optional[float] = None,
        max_restarts: Optional[int] = None,
        output_check: Optional[Callable[[np.ndarray], None]] = None,
        dtype=np.float64,
        async_spec: Optional[AsyncTransformSpec] = None,
        pipeline_depth: Optional[int] = None,
        queue=None,
        device_label: Optional[str] = None,
    ):
        if max_batch_rows < 1:
            raise ValueError("max_batch_rows must be >= 1")
        self.transform_fn = transform_fn
        self.output_check = output_check
        self.name = name
        # The replica tier (serve/placement.py): which device this
        # batcher's dispatches land on — per-device batch attribution
        # (devmon) and the per-replica batches counter both key on it.
        # None = the pre-replica single-device behavior bit-for-bit.
        self.device_label = device_label
        self.max_batch_rows = int(max_batch_rows)
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.max_queue_depth = int(max_queue_depth)
        self.dtype = np.dtype(dtype)
        self.async_spec = async_spec
        if pipeline_depth is None:
            pipeline_depth = pipeline_depth_from_env()
        # Only an async spec can overlap batches; the blocking path keeps
        # the exact pre-pipeline serial loop (depth 1).
        self.pipeline_depth = (max(int(pipeline_depth), 1)
                               if async_spec is not None else 1)
        if async_spec is not None:
            self._stage_fn = async_spec.stage
            self._dispatch_fn = async_spec.dispatch
            self._complete_fn = async_spec.complete
            self._report_algo: Optional[str] = async_spec.algo
            self._precision = async_spec.precision
        else:
            self._stage_fn = _identity
            self._dispatch_fn = self._call_transform
            self._complete_fn = _identity
            self._report_algo = None
            self._precision = "native"
        # Worker supervision knobs: one batch exceeding the budget
        # between dispatch and completion declares the worker wedged
        # (None → the flight recorder's transform budget; <= 0 / inf
        # disables wedge detection); max_restarts bounds crash/wedge
        # recoveries (None = unlimited).
        if worker_budget_s is None:
            self.worker_budget_s = flight.transform_budget_seconds()
        elif worker_budget_s <= 0:
            self.worker_budget_s = float("inf")
        else:
            self.worker_budget_s = float(worker_budget_s)
        self.max_restarts = (None if max_restarts is None
                             else int(max_restarts))
        if buckets:
            self.buckets: Tuple[int, ...] = tuple(
                sorted(int(b) for b in buckets))
            # An explicit ladder is a compiled-signature CONTRACT: never
            # build a batch the ladder cannot hold, or the pow-2 fallback
            # would compile unwarmed shapes under live traffic.
            self.max_batch_rows = min(self.max_batch_rows, self.buckets[-1])
        else:
            self.buckets = default_buckets(self.max_batch_rows)
        # The queue DISCIPLINE is pluggable (``serve.scheduler``):
        # FifoQueue is the pre-scheduler deque bit-for-bit; the engine
        # passes a FairQueue for weighted-fair multi-tenant dispatch.
        self._queue = queue if queue is not None else FifoQueue()
        # queue-wait estimate: EWMA updated at every pop, decayed toward
        # 0 while idle (an estimate frozen at the last overload would
        # keep the shed controller shedding an empty queue). Worker-
        # thread-only writes; readers tolerate torn staleness.
        self._wait_ewma = 0.0
        self._wait_ewma_at = time.monotonic()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._closed = False
        self._crashed = False
        self._generation = 1
        self._restarts = 0
        self._inflight: List[_InFlight] = []
        self._restart_pause_s = 0.02  # crash-storm brake
        # Union device-busy accounting for the pipeline occupancy
        # metrics: its own tiny lock so completion never contends with
        # the queue lock.
        self._busy_lock = threading.Lock()
        self._busy_active = 0
        self._busy_marker = 0.0
        self._overlap_marker = 0.0
        # resolved once like the metric family handles below — the
        # execute path must not take the monitor's global lock per batch
        self._devmon = get_device_monitor()
        self._ledger = accounting.get_ledger()
        self._declare_metrics()
        self._worker = self._spawn_worker()

    def _declare_metrics(self) -> None:
        """Create this model's serving series up front (a dashboard should
        see a flat 0, not an absent series) and keep the family handles —
        the hot path increments through them instead of re-resolving
        name/help/labels per call."""
        reg = get_registry()
        self._m_depth = reg.gauge(
            "sparkml_serve_queue_depth",
            "requests waiting in the serving queue", ("model",),
        )
        self._m_depth.set(0, model=self.name)
        self._m_occupancy = reg.gauge(
            "sparkml_serve_batch_occupancy",
            "real rows / bucket rows of the last executed batch",
            ("model",),
        )
        self._m_occupancy.set(0.0, model=self.name)
        self._m_waste = reg.gauge(
            "sparkml_serve_padding_waste",
            "fraction of the last executed batch that was padding",
            ("model",),
        )
        self._m_waste.set(0.0, model=self.name)
        self._m_expired = reg.counter(
            "sparkml_serve_deadline_expired_total",
            "requests shed because their deadline expired before serving",
            ("model",),
        )
        self._m_expired.inc(0, model=self.name)
        self._m_rejected = reg.counter(
            "sparkml_serve_rejected_total",
            "requests rejected by admission control (queue full)",
            ("model",),
        )
        self._m_rejected.inc(0, model=self.name)
        self._m_requests = reg.counter(
            "sparkml_serve_requests_total",
            "serving requests by outcome", ("model", "outcome"),
        )
        self._m_batches = reg.counter(
            "sparkml_serve_batches_total",
            "coalesced batches executed", ("model",),
        )
        self._m_batch_rows = reg.counter(
            "sparkml_serve_batch_rows_total",
            "real (caller) rows executed in coalesced batches", ("model",),
        )
        self._m_bucket_rows = reg.counter(
            "sparkml_serve_bucket_rows_total",
            "bucket (padded-shape) rows executed — with "
            "sparkml_serve_batch_rows_total this yields mean occupancy",
            ("model",),
        )
        self._m_coalesced = reg.counter(
            "sparkml_serve_coalesced_requests_total",
            "requests served via coalesced batches", ("model",),
        )
        self._m_stage = reg.summary(
            "sparkml_serve_stage_latency_seconds",
            "per-stage serving latency (queue wait, stage, dispatch, "
            "sync, and the combined execute)", ("model", "stage"),
        )
        self._m_errors = reg.counter(
            "sparkml_serve_errors_total",
            "serving errors by type: batch failures (exception class), "
            "worker crashes/wedges, breaker rejections", ("model", "error"),
        )
        self._m_errors.inc(0, model=self.name, error="worker_crashed")
        self._m_shed_tenant = reg.counter(
            "sparkml_serve_shed_total",
            "requests shed by the adaptive overload controller, by "
            "tenant and reason", ("tenant", "reason"),
        )
        self._m_restarts = reg.counter(
            "sparkml_serve_worker_restarts_total",
            "batcher worker restarts after a crash or watchdog-declared "
            "wedge", ("model",),
        )
        self._m_restarts.inc(0, model=self.name)
        self._m_busy = reg.counter(
            "sparkml_serve_device_busy_seconds_total",
            "union wall-clock with >= 1 batch in flight (dispatched, not "
            "yet completed) — the numerator of the bench's "
            "pipeline_overlap_fraction", ("model",),
        )
        self._m_busy.inc(0, model=self.name)
        self._m_overlap = reg.counter(
            "sparkml_serve_pipeline_overlap_seconds_total",
            "wall-clock with >= 2 batches in flight (stage/transfer of "
            "batch N+1 overlapping compute of batch N)", ("model",),
        )
        self._m_overlap.inc(0, model=self.name)
        self._m_window = reg.gauge(
            "sparkml_serve_pipeline_inflight",
            "batches currently in the async in-flight window", ("model",),
        )
        self._m_window.set(0, model=self.name)
        self._m_replica_batches = reg.counter(
            "sparkml_serve_replica_batches_total",
            "coalesced batches served per (model, device) replica — the "
            "multi-device tier's per-replica dispatch evidence",
            ("model", "device"),
        )
        if self.device_label is not None:
            self._m_replica_batches.inc(0, model=self.name,
                                        device=self.device_label)

    # -- submission --------------------------------------------------------

    def submit(self, rows: np.ndarray,
               deadline: Optional[float] = None,
               trace_ctx: Optional[tracectx.TraceContext] = None,
               tenant: str = "default", priority: str = INTERACTIVE,
               over_quota: bool = False,
               ) -> _Request:
        """Enqueue a (n, d) request; returns the latch to ``wait`` on.

        Rows are coerced ONCE, here, to the model's transform ``dtype`` —
        a caller already sending matching rows pays no copy (the old
        unconditional float64 coercion doubled copy bytes for f32
        models). ``trace_ctx`` is the caller's captured ``TraceContext``
        (rule 5: every enqueue hands its identity across the queue —
        ``None`` only for untraced internal traffic).
        ``tenant``/``priority``/``over_quota`` are the admission
        verdict the fair scheduler orders by. Raises ``QueueFull`` past
        ``max_queue_depth`` (admission control) and ``BatcherClosed``
        after ``close()`` — both BEFORE the request occupies queue
        memory. Under the fair queue, a FULL queue may instead
        **preempt** a strictly lower-ranked queued request: the victim
        is shed with ``ShedLoad`` (counted, audited) and the arrival
        takes its slot — interactive traffic cannot be starved by a
        queue full of batch work.
        """
        rows = np.asarray(rows, dtype=self.dtype)
        if rows.ndim == 1:
            rows = rows[None, :]
        if rows.ndim != 2 or rows.shape[0] == 0:
            raise ValueError(
                f"expected a non-empty (n, d) request, got shape {rows.shape}"
            )
        if rows.shape[0] > self.max_batch_rows:
            raise ValueError(
                f"{self.name}: request of {rows.shape[0]} rows exceeds "
                f"max_batch_rows {self.max_batch_rows} — split it, or "
                "configure a larger top bucket"
            )
        req = _Request(rows, deadline,
                       trace_ctx=trace_ctx or tracectx.capture(),
                       tenant=tenant, priority=priority,
                       over_quota=over_quota)
        victim: Optional[_Request] = None
        with self._not_empty:
            if self._closed:
                raise BatcherClosed(f"batcher {self.name!r} is closed")
            if self._crashed or not self._worker.is_alive():
                # Fail FAST: a request accepted into a dead batcher's
                # queue would hang until its deadline (or forever).
                self._crashed = True
                self._m_requests.inc(model=self.name, outcome="error")
                self._m_errors.inc(model=self.name, error="worker_crashed")
                raise WorkerCrashed(
                    f"{self.name}: batcher worker is dead (restart "
                    "budget exhausted) — evict and re-create the batcher"
                )
            if len(self._queue) >= self.max_queue_depth:
                # Priority preemption: a strictly lower-ranked queued
                # request may be evicted for the arrival (FairQueue
                # only; FifoQueue always declines — the pre-scheduler
                # reject-the-newcomer behavior, bit-for-bit).
                victim = self._queue.select_victim(req)
                if victim is None:
                    self._m_requests.inc(model=self.name,
                                         outcome="rejected")
                    self._m_rejected.inc(model=self.name)
                    raise QueueFull(
                        f"{self.name}: queue depth {len(self._queue)} >= "
                        f"max_queue_depth {self.max_queue_depth}"
                    )
            self._queue.append(req)
            self._record_depth()
            self._not_empty.notify()
        if victim is not None:
            self._shed_preempted(victim)
        return req

    def _shed_preempted(self, victim: _Request) -> None:
        """Resolve a queue-full preemption victim: shed with
        ``ShedLoad`` (the arrival outranked it), counted per tenant and
        as a distinct ``load_shed`` error — never a silent drop."""
        with tracectx.activate(victim.trace_ctx):
            # the victim's queue-wait interval still lands in its trace
            # — the 503 it sees must be correlatable with how long it
            # actually waited, same as every other queue-exit path
            self._record_queue_span(victim, shed=True, error="ShedLoad")
            victim.set_error(ShedLoad(
                f"{self.name}: preempted from a full queue by a "
                "higher-priority arrival",
                retry_after=min(self.queue_wait_estimate() + 1.0,
                                retry_after_cap()),
                reason="preempted", tenant=victim.tenant,
            ))
        self._m_requests.inc(model=self.name, outcome="shed")
        self._m_errors.inc(model=self.name, error="load_shed")
        self._m_shed_tenant.inc(tenant=victim.tenant, reason="preempted")

    def queue_wait_estimate(self) -> float:
        """The live queue-wait estimate (seconds): an EWMA over recent
        pop-time waits, decayed toward zero while the queue is idle —
        one overload burst must not keep reading as pressure forever.
        Feeds the shed controller and the HTTP ``Retry-After``."""
        age = max(time.monotonic() - self._wait_ewma_at, 0.0)
        return self._wait_ewma * (0.5 ** (age / 2.0))

    def _note_queue_wait(self, wait_s: float) -> None:
        self._wait_ewma = (0.8 * self.queue_wait_estimate()
                           + 0.2 * max(wait_s, 0.0))
        self._wait_ewma_at = time.monotonic()

    def depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def load(self) -> int:
        """Queued requests plus in-flight batches — the placement
        tier's least-loaded signal for this replica."""
        with self._lock:
            return len(self._queue) + len(self._inflight)

    def dead(self) -> bool:
        """Restart budget exhausted (or the worker died with none left):
        every submit fails fast. The engine replaces a dead batcher with
        a fresh one on the next request for its model — otherwise the
        breaker's half-open probe could never reach the device again."""
        with self._lock:
            return self._crashed

    def closed(self) -> bool:
        """Whether ``close()`` ran — how the autoscale reaper and a
        scale-up's un-retire tell a drained-and-reaped batcher from a
        merely idle one."""
        with self._lock:
            return self._closed

    # -- lifecycle ---------------------------------------------------------

    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop accepting; with ``drain`` the worker serves what's already
        queued (draining its in-flight window), otherwise queued requests
        are failed with ``BatcherClosed``. Idempotent.

        Ends with a sweep-under-the-lock: anything still queued after
        the worker joined (it crashed, wedged, or the join timed out —
        the eviction race that used to drop error propagation) is failed
        with ``BatcherClosed``, and batches still IN FLIGHT on a worker
        that outlived the join (wedged with wedge detection disabled) are
        failed with ``WorkerCrashed`` — no request ever hangs to its
        wait timeout."""
        with self._not_empty:
            self._closed = True
            if not drain:
                while self._queue:
                    req = self._queue.popleft()
                    with tracectx.activate(req.trace_ctx):
                        req.set_error(
                            BatcherClosed(
                                f"batcher {self.name!r} shut down")
                        )
                self._record_depth()
            self._not_empty.notify_all()
        self._worker.join(timeout=timeout)
        with self._not_empty:
            leftovers = []
            while self._queue:
                leftovers.append(self._queue.popleft())
            if leftovers:
                self._record_depth()
            stuck: List[_InFlight] = []
            if self._worker.is_alive() and self._inflight:
                # join timed out with batches on the wedged worker:
                # retire the generation (its late results are discarded)
                # and fail the window instead of leaving it to hang.
                stuck = list(self._inflight)
                self._inflight = []
                self._generation += 1
        if stuck:
            self._disarm_entries(stuck)
            self._fail_requests(
                [req for e in stuck for req in e.batch],
                WorkerCrashed(
                    f"{self.name}: batcher closed while its worker was "
                    "stuck in a transform; in-flight requests failed fast"
                ))
        if leftovers:
            self._fail_requests(
                leftovers,
                BatcherClosed(
                    f"batcher {self.name!r} shut down before serving "
                    "queued requests"),
                error_label="batcher_closed",
            )

    # -- the worker --------------------------------------------------------

    def _pop_live(self) -> Optional[_Request]:
        """Pop the next unexpired request; shed expired ones (counted,
        errored) without touching the device. Caller holds the lock.

        The fair queue first sweeps expired entries from the WHOLE
        queue (``pop_expired``): under pressure the interactive-first
        pick never reaches queued batch work, so an expired batch
        request would otherwise neither serve nor shed — its client
        hanging to the wait timeout while the dead entry pins queue
        depth (and the pressure signal with it). FIFO's sweep is a
        no-op: its head always drains, preserving the pre-scheduler
        behavior exactly."""
        for expired in self._queue.pop_expired():
            self._shed(expired)
        while self._queue:
            req = self._queue.popleft()
            if req.expired():
                self._shed(req)
                continue
            return req
        return None

    def _shed(self, req: _Request) -> None:
        self._note_queue_wait(time.monotonic() - req.enqueued)
        with tracectx.activate(req.trace_ctx):
            self._record_queue_span(req, shed=True)
            req.set_error(DeadlineExpired(
                f"{self.name}: deadline expired after "
                f"{time.monotonic() - req.enqueued:.3f}s in queue"
            ))
        self._m_requests.inc(model=self.name, outcome="expired")
        self._m_expired.inc(model=self.name)

    def _record_queue_span(self, req: _Request, shed: bool = False,
                           error: str = "DeadlineExpired") -> None:
        """File the queue-wait interval into the REQUEST's trace (the
        enqueue thread stamped t0; this — pop/shed — is t1)."""
        ctx = req.trace_ctx
        if ctx is None:
            return
        args = {"model": self.name, "rows": req.n}
        if shed:
            args["error"] = error
        spans_mod.record_event(
            f"serve:queue:{self.name}",
            req.enqueued_perf, time.perf_counter(),
            trace_id=ctx.trace_id, parent_span_id=ctx.span_id,
            **args,
        )

    def _spawn_worker(self) -> threading.Thread:
        """Start a worker for the CURRENT generation. fresh=True: the
        worker outlives the request whose call created this batcher —
        it must not inherit that request's context."""
        gen = self._generation
        worker = tracectx.traced_thread(
            self._supervise, name=f"sparkml-serve-{self.name}-g{gen}",
            daemon=True, fresh=True, kwargs={"gen": gen},
        )
        worker.start()
        return worker

    def _supervise(self, gen: int) -> None:
        """The worker thread's entry point: a crash escaping the serve
        loop fails the in-flight window fast and hands off to a
        replacement worker (a fresh thread) instead of dying silently."""
        try:
            self._run(gen)
        except BaseException as exc:  # noqa: BLE001 - supervised
            self._m_errors.inc(model=self.name, error="worker_crashed")
            self._on_worker_crash(exc, gen)

    def _on_worker_crash(self, exc: BaseException, gen: int) -> None:
        """Fail the crashed generation's in-flight window fast, then
        either hand off to a replacement worker or mark the batcher
        dead (restart budget exhausted — queued requests fail too)."""
        with self._not_empty:
            if gen != self._generation:
                return  # the wedge handler already took over
            stranded = list(self._inflight)
            self._inflight = []
            self._generation += 1
            can_restart = not self._closed and (
                self.max_restarts is None
                or self._restarts < self.max_restarts
            )
            to_fail = [req for e in stranded for req in e.batch]
            if not can_restart:
                self._crashed = True
                while self._queue:
                    to_fail.append(self._queue.popleft())
                self._record_depth()
                self._not_empty.notify_all()
        self._disarm_entries(stranded)
        self._fail_requests(to_fail, WorkerCrashed(
            f"{self.name}: batcher worker crashed "
            f"({type(exc).__name__}: {exc}); in-flight requests failed fast"
        ))
        if can_restart:
            time.sleep(self._restart_pause_s)
            with self._not_empty:
                if not self._closed:
                    self._restarts += 1
                    self._worker = self._spawn_worker()
                    self._m_restarts.inc(model=self.name)

    def _declare_wedged(self, gen: int, entry: _InFlight) -> None:
        """Watchdog ``on_expire`` hook (runs on the watchdog thread): one
        batch has sat between dispatch and completion past
        ``worker_budget_s`` — the worker is stuck. Fail the ENTIRE
        in-flight window fast (only the stuck thread could have drained
        the later entries), abandon the thread (its generation is retired
        — late results cannot resolve anything), and spawn a replacement
        with a fresh staging pool so the queue keeps draining."""
        with self._not_empty:
            if gen != self._generation or entry not in self._inflight:
                return  # resolved (or already handled) in the meantime
            stranded = list(self._inflight)
            self._inflight = []
            self._generation += 1
            can_restart = not self._closed and (
                self.max_restarts is None
                or self._restarts < self.max_restarts
            )
            to_fail = [req for e in stranded for req in e.batch]
            if can_restart:
                self._restarts += 1
                self._worker = self._spawn_worker()
            else:
                self._crashed = True
                while self._queue:
                    to_fail.append(self._queue.popleft())
                self._record_depth()
                self._not_empty.notify_all()
        self._disarm_entries(stranded, skip=entry)
        self._fail_requests(to_fail, WorkerCrashed(
            f"{self.name}: batcher worker wedged — one batch exceeded "
            f"the {self.worker_budget_s:g}s watchdog budget; the "
            "in-flight window failed fast"
        ))
        if can_restart:
            self._m_restarts.inc(model=self.name)

    def _disarm_entries(self, entries: List[_InFlight],
                        skip: Optional[_InFlight] = None) -> None:
        """Release stranded entries: flush their device-busy intervals
        (a stranded batch must not leave the pipeline-occupancy
        accounting elevated forever) and disarm their watchdogs, both
        OUTSIDE the batcher lock (the watchdog thread takes our lock in
        ``on_expire`` — taking its lock while holding ours would invert
        the order)."""
        for e in entries:
            self._note_complete(e)
            if e is skip or e.watchdog is None:
                continue
            flight.get_watchdog().disarm(e.watchdog)
            e.watchdog = None

    def _fail_requests(self, requests: List[_Request],
                       exc: BaseException,
                       error_label: str = "worker_crashed") -> None:
        for req in requests:
            with tracectx.activate(req.trace_ctx):
                req.set_error(exc)
        if requests:
            self._m_requests.inc(len(requests), model=self.name,
                                 outcome="error")
            self._m_errors.inc(len(requests), model=self.name,
                               error=error_label)

    def _run(self, gen: int) -> None:
        # Each worker generation owns its staging pool, so an abandoned
        # (wedged) predecessor can never scribble into a buffer this
        # generation stages from. Slots cover the window plus the
        # transfer possibly still reading the previous buffer. The pool
        # exists only for the async pipeline: its `complete` step always
        # materializes fresh host memory, so reusing the staging buffer
        # is safe — whereas a blocking transform_fn may return (views
        # of) its input, and per-request result slices must never alias
        # a buffer the next batch will overwrite.
        staging = (StagingPool(self.dtype,
                               slots=self.pipeline_depth + 2)
                   if self.async_spec is not None else None)
        window: collections.deque = collections.deque()
        while True:
            batch: Optional[List[_Request]] = None
            with self._not_empty:
                if gen != self._generation:
                    return  # abandoned after a wedge; a replacement runs
                while not self._queue and not self._closed and not window:
                    self._not_empty.wait(timeout=0.1)
                    if gen != self._generation:
                        return
                first = self._pop_live()
                if first is not None:
                    batch = [first]
                    rows = first.n
                    # Linger: coalesce until the row cap or the wait
                    # budget — but never idle-wait while batches are in
                    # flight: with the device already busy, dispatching
                    # what's queued NOW and then draining the oldest
                    # batch beats holding its result for stragglers.
                    t0 = time.monotonic()
                    while rows < self.max_batch_rows:
                        remaining = self.max_wait_s - (
                            time.monotonic() - t0)
                        if not self._queue:
                            if remaining <= 0 or self._closed or window:
                                break
                            self._not_empty.wait(timeout=remaining)
                            continue
                        nxt = self._queue.peek()
                        if nxt.expired():
                            self._queue.popleft()
                            self._shed(nxt)
                            continue
                        if rows + nxt.n > self.max_batch_rows:
                            break  # leave it for the next batch
                        self._queue.popleft()
                        batch.append(nxt)
                        rows += nxt.n
                    self._record_depth()
                    # From here the batch is "in flight": registered
                    # UNDER the lock, before any fault-prone work, so a
                    # crash or wedge handler fails exactly these
                    # requests — a crash between pop and dispatch can
                    # never strand them.
                    entry = _InFlight(
                        batch, tracectx.new_context(model=self.name))
                    self._inflight.append(entry)
                elif not window:
                    if self._closed:
                        return
                    self._record_depth()
                    continue
            if batch is None:
                # Queue empty with batches in flight: drain the oldest —
                # the completion step, the pipeline's only host sync.
                self._complete_oldest(window, gen)
                continue
            spec = fault_plane().worker_fault(self.name)
            if spec is not None:
                raise InjectedWorkerCrash(
                    f"injected worker crash on {self.name!r}"
                )
            entry = self._stage_dispatch(entry, gen, staging)
            if entry is not None:
                window.append(entry)
            while len(window) >= self.pipeline_depth:
                self._complete_oldest(window, gen)
            if gen != self._generation:
                return

    def _call_transform(self, matrix: np.ndarray):
        """The blocking (no-async-spec) dispatch: one model call."""
        return self.transform_fn(matrix)

    def _stage_dispatch(self, entry: _InFlight, gen: int,
                        staging: Optional[StagingPool],
                        ) -> Optional[_InFlight]:
        """Stage (pad into a reusable buffer + start the host→device
        transfer) and async-dispatch one coalesced batch (already
        registered in the supervision window by ``_run``). Returns the
        in-flight entry, or None when the batch failed synchronously —
        in which case only ITS members are failed and the pipeline keeps
        running (the mid-window-failure invariant)."""
        batch = entry.batch
        with self._not_empty:
            if gen != self._generation:
                # a wedge handler retired this generation between pop
                # and dispatch — it already failed these requests
                return None
        now = time.monotonic()
        stage_metric = self._m_stage
        for req in batch:
            tid = req.trace_ctx.trace_id if req.trace_ctx else None
            wait = now - req.enqueued
            self._note_queue_wait(wait)
            stage_metric.observe(wait, trace_id=tid,
                                 model=self.name, stage="queue")
            self._record_queue_span(req)
        # The fan-in edge: ONE coalesced dispatch runs in its own batch
        # trace whose `links` name every member request's trace, so each
        # member's assembled tree grafts the shared batch subtree in
        # (Dapper's fan-in span).
        member_ids: List[str] = []
        for req in batch:
            if req.trace_ctx and req.trace_ctx.trace_id not in member_ids:
                member_ids.append(req.trace_ctx.trace_id)
        entry.member_ids = tuple(member_ids)
        if self._report_algo:
            # Async batches bypass the models' decorated entry points, so
            # the batcher publishes the per-batch TransformReport itself
            # — stage/dispatch/sync phase split, latency sketch, numerics.
            entry.report = obs_serving.PipelineTransform(
                self._report_algo, trace_id=entry.ctx.trace_id,
                precision=self._precision,
            )
        try:
            # Wedge watchdog: armed BEFORE the host→device transfer —
            # a device backend hang can block inside device_put
            # itself, so a budget armed after the stage step would never
            # see it. The budget expiring fails the in-flight window
            # fast (on_expire) and dumps a flight artifact: a silent
            # hang becomes a sub-budget WorkerCrashed plus a dump. Armed per batch, stage → completion.
            if self.worker_budget_s and self.worker_budget_s != float("inf"):
                entry.watchdog = flight.get_watchdog().arm(
                    f"serve_worker:{self.name}", self.worker_budget_s,
                    info={"model": self.name, "requests": len(batch),
                          "rows": sum(r.n for r in batch)},
                    on_expire=lambda: self._declare_wedged(gen, entry),
                )
            t0 = time.perf_counter()
            if staging is not None:
                staged, n = staging.fill([r.rows for r in batch],
                                         self.buckets)
            else:
                # blocking path: a fresh matrix per batch (the pre-
                # pipeline allocation) — transform_fn may return views
                # of its input, and result slices must not alias a
                # reused buffer
                matrix = (batch[0].rows if len(batch) == 1
                          else np.concatenate([r.rows for r in batch],
                                              axis=0))
                staged, n = pad_to_bucket(matrix, self.buckets)
            entry.n = n
            entry.bucket = int(staged.shape[0])
            entry.features = int(staged.shape[1])
            entry.bytes_in = int(staged.nbytes)
            handle = self._stage_fn(staged)
            entry.stage_seconds = time.perf_counter() - t0
            t1 = time.perf_counter()
            self._note_dispatch(entry)
            with tracectx.activate(entry.ctx), span(
                f"serve:batch:{self.name}",
                trace_id=entry.ctx.trace_id, links=entry.member_ids,
                requests=len(batch), rows=n, bucket=entry.bucket,
            ):
                entry.batch_span_id = spans_mod.current_span_id()
                if entry.report is not None:
                    with entry.report.dispatch_scope():
                        entry.handle = self._dispatch_fn(handle)
                else:
                    entry.handle = self._dispatch_fn(handle)
            entry.dispatch_seconds = time.perf_counter() - t1
            with self._not_empty:
                retired = gen != self._generation
            if retired:
                # A wedge handler retired this generation while we were
                # staging/dispatching. It already failed (and counted)
                # this entry's requests, but it could not see the
                # watchdog/busy state created above — release both here,
                # or an orphaned deadline later fires a spurious dump
                # and the pipeline-occupancy accounting stays elevated
                # forever.
                if entry.watchdog is not None:
                    flight.get_watchdog().disarm(entry.watchdog)
                    entry.watchdog = None
                self._note_complete(entry)
                return None
            return entry
        except Exception as exc:  # noqa: BLE001 - batch-level failure
            # Only THIS batch fails; the worker (and the rest of the
            # window) survives. Count it so failing batches are visible
            # as an error series, not silence (rule 6).
            self._m_errors.inc(model=self.name, error=type(exc).__name__)
            if entry.watchdog is not None:
                flight.get_watchdog().disarm(entry.watchdog)
                entry.watchdog = None
            self._note_complete(entry)
            stale = self._retire_entry(entry, gen)
            if entry.report is not None:
                entry.report.finish(error=exc)
            if not stale:
                for req in batch:
                    with tracectx.activate(req.trace_ctx):
                        req.set_error(exc)
                self._m_requests.inc(len(batch), model=self.name,
                                     outcome="error")
            return None

    def _retire_entry(self, entry: _InFlight, gen: int) -> bool:
        """Remove one entry from the supervision window; True when a
        crash/wedge handler already owned (and failed) it."""
        with self._not_empty:
            if gen != self._generation or entry not in self._inflight:
                return True
            self._inflight.remove(entry)
            return False

    def _complete_oldest(self, window: collections.deque,
                         gen: int) -> None:
        """Drain the oldest in-flight batch: host-sync its result,
        slice padding, run the output check, resolve every member."""
        entry: _InFlight = window.popleft()
        out = None
        err: Optional[BaseException] = None
        t0 = time.perf_counter()
        try:
            out = self._complete_batch(entry)
            if out.shape[0] < entry.n:
                raise ValueError(
                    f"{self.name}: transform returned {out.shape[0]} rows "
                    f"for a batch of {entry.n}"
                )
            out = out[:entry.n]  # padding never leaks into any response
            if self.output_check is not None:
                self.output_check(out)
        except Exception as exc:  # noqa: BLE001 - batch-level failure
            self._m_errors.inc(model=self.name, error=type(exc).__name__)
            err = exc
        entry.sync_seconds = time.perf_counter() - t0
        if entry.watchdog is not None:
            flight.get_watchdog().disarm(entry.watchdog)
            entry.watchdog = None
        busy_delta = self._note_complete(entry)
        # per-device occupancy attribution (obs.devmon — never raises):
        # the placement tier reads its least-loaded signal from this.
        # Union busy time, so overlapping window entries are not
        # double-counted; a replica batcher attributes to ITS device.
        self._devmon.note_batch(self.name, busy_delta,
                                device=self.device_label)
        # same seam, same number, into the per-model cost ledger — so
        # reconcile() can hold the two attributions to each other
        self._ledger.note_batch_seconds(self.name, busy_delta,
                                        device=self.device_label)
        if self._retire_entry(entry, gen):
            # The watchdog declared this window wedged (and failed it)
            # while the result was still in flight; the late result is
            # discarded — first writer won.
            return
        if err is not None:
            if entry.report is not None:
                entry.report.finish(error=err)
            for req in entry.batch:
                with tracectx.activate(req.trace_ctx):
                    req.set_error(err)
            self._m_requests.inc(len(entry.batch), model=self.name,
                                 outcome="error")
            return
        # Batch telemetry BEFORE the latches resolve: the moment a
        # member's latch releases, its HTTP response can land and the
        # client may assemble its trace — the fan-in transform span and
        # the serve:sync event must already be in the span ring by then
        # (a resolve-first ordering made the assembled tree race the
        # worker thread and intermittently miss the transform span).
        # Exception-guarded: the reorder put telemetry UPSTREAM of the
        # latch resolution, and the entry is already retired from the
        # supervision window — a telemetry raise here would otherwise
        # strand every member to its wait timeout with the results
        # computed and lost.
        try:
            self._record_batch(entry.n, entry.bucket, len(entry.batch))
            self._record_pipeline(entry, out)
        except Exception:  # noqa: BLE001 - telemetry, not control flow
            self._m_errors.inc(model=self.name, error="batch_telemetry")
        offset = 0
        for req in entry.batch:
            # resolve under the member's own context: anything recorded
            # during latch release attributes to ITS trace, not a
            # neighbour's (rule 5's "response future resolution" leg)
            with tracectx.activate(req.trace_ctx):
                req.set_result(out[offset:offset + req.n])
            offset += req.n
        self._m_requests.inc(len(entry.batch), model=self.name,
                             outcome="ok")

    def _complete_batch(self, entry: _InFlight) -> np.ndarray:
        """THE pipeline's designated host-sync point: the only place in
        the worker loop allowed to force a device value to host (rule 9
        of ``scripts/check_instrumentation.py`` rejects ``np.asarray`` /
        ``block_until_ready`` anywhere else in this loop — a future edit
        cannot silently re-serialize the pipeline)."""
        return np.asarray(self._complete_fn(entry.handle))

    # -- pipeline accounting -----------------------------------------------

    def _note_dispatch(self, entry: _InFlight) -> None:
        """Open ``entry``'s in-flight interval. ``dispatched`` flips
        under the same lock the flush reads it under, so a wedge handler
        racing this exact instant still sees a consistent pair."""
        now = time.perf_counter()
        with self._busy_lock:
            entry.dispatched = True
            self._busy_active += 1
            if self._busy_active == 1:
                self._busy_marker = now
            elif self._busy_active == 2:
                self._overlap_marker = now
            # gauge set INSIDE the lock: a set landing after a racing
            # thread's later set would leave the inflight series stale
            self._m_window.set(self._busy_active, model=self.name)

    def _note_complete(self, entry: _InFlight) -> float:
        """Close ``entry``'s in-flight interval; flush the union
        device-busy (and >=2-deep overlap) time accrued since the last
        flush. Exactly-once per entry (``dispatched`` flips under the
        busy lock): completion, the dispatch failure path, AND the
        crash/wedge/close handlers all route here, so a stranded entry
        can never leave the busy accounting elevated — and a late
        completion by an abandoned worker can never double-flush."""
        now = time.perf_counter()
        with self._busy_lock:
            if not entry.dispatched or self._busy_active <= 0:
                return 0.0
            entry.dispatched = False
            busy = max(now - self._busy_marker, 0.0)
            overlap = 0.0
            if self._busy_active >= 2:
                overlap = max(now - self._overlap_marker, 0.0)
                self._overlap_marker = now
            self._busy_active -= 1
            self._busy_marker = now
            self._m_window.set(self._busy_active, model=self.name)
        if busy > 0:
            self._m_busy.inc(busy, model=self.name)
        if overlap > 0:
            self._m_overlap.inc(overlap, model=self.name)
        return busy

    def _record_pipeline(self, entry: _InFlight, out: np.ndarray) -> None:
        """Completion-side telemetry for one served batch: the
        stage/dispatch/sync latency split, the ``serve:sync`` trace event,
        and (async batches) the per-batch TransformReport."""
        stage = self._m_stage
        tid = entry.ctx.trace_id
        execute = (entry.stage_seconds + entry.dispatch_seconds
                   + entry.sync_seconds)
        stage.observe(execute, trace_id=tid, model=self.name,
                      stage="execute")
        stage.observe(entry.stage_seconds, trace_id=tid, model=self.name,
                      stage="stage")
        stage.observe(entry.dispatch_seconds, trace_id=tid,
                      model=self.name, stage="dispatch")
        stage.observe(entry.sync_seconds, trace_id=tid, model=self.name,
                      stage="sync")
        now = time.perf_counter()
        spans_mod.record_event(
            f"serve:sync:{self.name}",
            now - entry.sync_seconds, now,
            trace_id=tid,
            parent_span_id=entry.batch_span_id or entry.ctx.span_id,
            model=self.name, rows=entry.n,
        )
        if entry.report is not None:
            entry.report.add_phase("stage", entry.stage_seconds)
            entry.report.add_phase("dispatch", entry.dispatch_seconds)
            entry.report.add_phase("sync", entry.sync_seconds)
            entry.report.finish(out, rows=entry.n,
                                features=entry.features,
                                bytes_in=entry.bytes_in,
                                parent_span_id=entry.batch_span_id)

    # -- metrics -----------------------------------------------------------

    def _record_depth(self) -> None:
        self._m_depth.set(len(self._queue), model=self.name)

    def _record_batch(self, real_rows: int, bucket: int,
                      n_requests: int) -> None:
        self._m_occupancy.set(
            real_rows / bucket if bucket else 0.0, model=self.name)
        self._m_waste.set(padding_waste(real_rows, bucket), model=self.name)
        self._m_batches.inc(model=self.name)
        self._m_batch_rows.inc(real_rows, model=self.name)
        self._m_bucket_rows.inc(bucket, model=self.name)
        self._m_coalesced.inc(n_requests, model=self.name)
        if self.device_label is not None:
            self._m_replica_batches.inc(model=self.name,
                                        device=self.device_label)

    def expected_signatures(self) -> int:
        """How many distinct compiled shapes steady-state traffic through
        this batcher can produce (= the bucket count)."""
        return len(self.buckets)

    def bucket_for_rows(self, n: int) -> int:
        return bucket_for(n, self.buckets)
