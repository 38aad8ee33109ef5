"""Executor-side DEVICE aggregation: Arrow batches → stats on this
executor's accelerator.

The reference's defining architecture puts the accelerator on every
executor: each Spark partition is centered and multiplied on that
executor's GPU (``RapidsRowMatrix.scala:168-202``, native GEMM
``rapidsml_jni.cu:172-258``), with ``spark.executor.resource.gpu``
scheduling the chips. ``spark/aggregate.py`` is the host-CPU (NumPy f64)
fallback of that plane; THIS module is the accelerator path: the partition
iterator streams through the device-resident donated accumulator
(``ops/streaming.py``) on the executor's own JAX device — the TPU is where
the O(rows·n²) Gram work happens, executor CPUs only densify Arrow
batches.

Executor device selection mirrors the reference's ``gpuId`` task-resource
semantics (``RapidsRowMatrix.scala:171-175``): ``device_id=-1`` resolves
through ``utils.resources.resolve_device_ordinal`` (task env /
``TPU_VISIBLE_CHIPS`` pinning from ``scripts/get_tpus_resources.sh``
discovery), so one chip-pinned executor process sees one chip.

The covariance statistics — a partition's (Σxxᵀ, Σx, n), of the rows or of
Z = [X | y] — ride the ONE streamed loop every ``PCA.fit`` runs
(``ops.streaming.stream_gram_stats``): the task's batch iterator becomes a
``BatchSource`` that reads record batches as views and re-blocks them into
``batchRows`` device batches in lent staging buffers, at most two puts are
in flight, the Gram kernel is ``accumulate_path``'s choice, and the spans
and counters are the loop's own. A task hands the row maker the moments as
fetched; the row is Arrow (``aggregate.stats_record_batch``: its float64
form by Arrow's cast, out of Arrow's pool), not n² Python floats.
The other statistics families below (Newton partials, Lloyd half-steps,
histograms) still pad their batches to power-of-two row buckets with a
validity mask, so an arbitrary partition produces a handful of compiled
shapes.
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, Iterable, Iterator, Optional

import numpy as np

from spark_rapids_ml_tpu.spark.aggregate import (
    note_host_array,
    stats_record_batch,
    vector_column_to_matrix,
)

_MIN_BUCKET = 256

# One executor task of the covariance plane, and inside it the hand-back of
# its statistics: spans in the profiler's trace and the ``obs.spans`` ring,
# seconds under the ``fit_timings_`` keys (``benchmarks/work/stage.py``
# mirrors the names).
SPAN_TASK = "stage:task"
SPAN_HANDBACK = "stage:handback"
PHASE_TASK = "stage/task"
PHASE_HANDBACK = "stage/handback"
# where a task leaves its timings and counters for a driver in the same
# process (``fit_report_.extra`` of the fit in flight, until the driver
# takes them): ``take_task_reports``
_TASK_REPORTS = "stage_task_reports"


def executor_device_available() -> bool:
    """True when this process can reach an ACCELERATOR JAX device (the
    CPU backend always registers a device, so its presence alone must not
    defeat the documented host-NumPy-f64 fallback of
    ``executorDevice='auto'``; import failure / no plugin / CPU-only all
    mean 'use the host path'). ``'on'`` forces the device path regardless
    — that is how CPU-device tests exercise it."""
    try:
        import jax

        return any(d.platform != "cpu" for d in jax.local_devices())
    except Exception:  # noqa: BLE001 - any init failure ⇒ host fallback
        return False


def _bucket_rows(m: int) -> int:
    b = _MIN_BUCKET
    while b < m:
        b *= 2
    return b


def _from_first_row(chunks: Iterable):
    """``chunks`` from the first that has a row on, or None for a partition
    without one (an empty partition adds nothing, and has no width to
    give)."""
    chunks = iter(chunks)
    for chunk in chunks:
        rows = chunk.num_rows if hasattr(chunk, "num_rows") else len(chunk)
        if rows:
            return itertools.chain([chunk], chunks)
    return None


def float64_stats_row(gram, col_sum, count) -> Dict[str, object]:
    """The stats row as a dict with the moments as float64 NumPy arrays
    (the documented form of ``partition_gram_stats_device`` and
    ``partition_xy_stats_device``), of the moments as a task fetched
    them."""
    if gram.dtype != np.float64:
        note_host_array("numpy")  # NumPy's float64 form is a new array
    return {"gram": np.asarray(gram, dtype=np.float64),
            "col_sum": np.asarray(col_sum, dtype=np.float64),
            "count": count}


def _device_gram_stats(chunks: Iterable, input_col: Optional[str], device,
                       dt, batch_rows: int = 0, precision=None,
                       row=float64_stats_row):
    """One executor task of the covariance plane: the partition's chunks
    (record batches, whose column ``input_col`` is read by ``data.arrow`` —
    a null or a ragged row raises, nothing is padded or dropped — or plain
    (m, n) arrays) through the one streamed loop in its one-pass form, then
    the hand-back: the moments fetched, and the stats row that
    ``row(gram=, col_sum=, count=)`` makes of them as they are (the
    device's dtype; the float64 form is the row maker's). None for a
    partition without a row."""
    import jax

    from spark_rapids_ml_tpu.data.batches import streaming_source
    from spark_rapids_ml_tpu.models.pca import SPAN_STREAMED_COV
    from spark_rapids_ml_tpu.ops.streaming import (
        SPAN_SYNC_COV,
        IngestTrace,
        put_copies,
        stream_gram_stats,
    )
    from spark_rapids_ml_tpu.utils.timing import PhaseTimer
    from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange

    timer = PhaseTimer()
    out, counters = None, None
    with timer.phase(PHASE_TASK), TraceRange(SPAN_TASK, TraceColor.PURPLE):
        chunks = _from_first_row(chunks)
        if chunks is not None:
            source = streaming_source(chunks, batch_rows, input_col)
            ingest = IngestTrace(timer, device)
            counters = ingest.counters
            with timer.phase("covariance"), TraceRange(
                    SPAN_STREAMED_COV, TraceColor.RED):
                stats = stream_gram_stats(source, dtype=dt,
                                          precision=precision, ingest=ingest)
                with ingest.sync(SPAN_SYNC_COV):
                    stats = jax.block_until_ready(stats)
                ingest.all_landed()  # every batch put is in ``stats``
            with timer.phase(PHASE_HANDBACK), TraceRange(
                    SPAN_HANDBACK, TraceColor.CYAN):
                if put_copies(device):
                    # a chip's memory is its own: the fetch is a new host
                    # array (the CPU backend hands out a view of its own)
                    note_host_array("numpy")
                out = row(gram=np.asarray(stats.gram),
                          col_sum=np.asarray(stats.col_sum),
                          count=int(stats.count))
    _report_task(timer, counters)
    return out


def _report_task(timer, counters: Optional[dict]) -> None:
    """Leave the task's seconds and the loop's counters (None: a partition
    without a row) where a driver in this process finds them."""
    from spark_rapids_ml_tpu.obs.report import current_fit

    fit = current_fit()  # the null context outside a fit: it keeps nothing
    fit.note(**{_TASK_REPORTS: [
        *fit.extra.get(_TASK_REPORTS, ()),
        {"timings": timer.as_dict(), "ingest": counters}]})


def take_task_reports() -> list:
    """The reports of the tasks that ran in this process inside the fit in
    flight (``[{"timings": …, "ingest": …}]``, in task order), taken off
    the fit's report; [] where the tasks ran elsewhere."""
    from spark_rapids_ml_tpu.obs.report import current_fit

    return current_fit().extra.pop(_TASK_REPORTS, [])


def _one_counter(a, b, key: str):
    """A counter of ``extra["ingest"]`` over two tasks: counts and seconds
    added; ``*_max`` / ``*_min``, ``chips``, the (unused) keep budget, the
    boundaries' ``hbm_bytes_in_use`` and a chip's ``last_landing_seconds``
    (each task's from its own first put: the longest task's) by their
    extreme; dicts key by key and ``per_chip`` chip by chip."""
    if a is None or b is None:
        return b if a is None else a
    if isinstance(b, dict):
        return {k: _one_counter(a.get(k), b.get(k),
                                key if key == "hbm_bytes_in_use" else k)
                for k in {**a, **b}}
    if isinstance(b, list):
        return [_one_counter(x, y, key) for x, y in zip(a, b)]
    if isinstance(b, str):  # a chip's name
        return a
    if key.endswith("_min"):
        return min(a, b)
    if key.endswith("_max") or key in ("chips", "keep_budget_bytes",
                                       "hbm_bytes_in_use",
                                       "last_landing_seconds"):
        return max(a, b)
    return a + b


def sum_ingest_counters(parts: Iterable[dict]) -> dict:
    """The tasks' ``extra["ingest"]`` as one fit's (``_one_counter``)."""
    return functools.reduce(lambda a, b: _one_counter(a, b, ""), parts)


def partition_gram_stats_device(
    batches: Iterable,
    input_col: str,
    device_id: int = -1,
    dtype: str = "auto",
    batch_rows: int = 0,
    precision=None,
    row=float64_stats_row,
) -> Iterator[Dict[str, object]]:
    """One partition's (Σxxᵀ, Σx, n), accumulated ON this executor's
    accelerator by the one streamed loop (``_device_gram_stats``).

    Same contract as ``aggregate.partition_gram_stats`` (the driver-side
    ``combine_stats`` is shared), with the Gram (n, n) and the column sums
    as float64 NumPy arrays, not lists. ``batch_rows`` is the estimator's
    ``batchRows`` (0 = auto-sized ≈128 MiB device batches), ``precision``
    its resolved ``gramPrecision``. The f64→f32 note: on accelerators the
    compute dtype follows the platform default (f32 on TPU) — the same
    documented precision envelope as every other streamed device fit in
    this repo.
    """
    from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype

    out = _device_gram_stats(
        batches, input_col, _resolve_device(device_id), _resolve_dtype(dtype),
        batch_rows, precision, row)
    if out is not None:
        yield out


def _xy_matrices(batches, features_col: str, label_col: str):
    for batch in batches:
        if hasattr(batch, "column"):
            x = vector_column_to_matrix(batch.column(features_col))
            y = np.asarray(batch.column(label_col).to_pylist(),
                           dtype=np.float64)
        else:
            x, y = batch
            x = np.asarray(x, dtype=np.float64)
            y = np.asarray(y, dtype=np.float64).reshape(-1)
        yield x, y


def partition_xy_stats_device(
    batches: Iterable,
    features_col: str,
    label_col: str,
    device_id: int = -1,
    dtype: str = "auto",
    row=float64_stats_row,
) -> Iterator[Dict[str, object]]:
    """Device counterpart of ``aggregate.partition_xy_stats``: the (n+1)²
    Gram of Z = [X | y] accumulated on this executor's accelerator (the
    augmented-column trick shared with the streamed LinearRegression), by
    the same loop as the PCA plane: each batch's Z is one chunk of it."""
    from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype

    chunks = (np.concatenate([x, y.reshape(-1, 1)], axis=1)
              for x, y in _xy_matrices(batches, features_col, label_col))
    out = _device_gram_stats(
        chunks, None, _resolve_device(device_id), _resolve_dtype(dtype),
        row=row)
    if out is not None:
        yield out


def partition_xy_stats_device_arrow(batches, features_col: str,
                                    label_col: str, device_id: int = -1):
    yield from partition_xy_stats_device(
        batches, features_col, label_col, device_id, row=stats_record_batch)


def partition_logreg_stats_device(
    batches: Iterable,
    features_col: str,
    label_col: str,
    w: np.ndarray,
    b: float,
    device_id: int = -1,
    dtype: str = "auto",
) -> Iterator[Dict[str, object]]:
    """Device counterpart of ``aggregate.partition_logreg_stats``: one
    partition's Newton/IRLS partials under the closure-broadcast (w, b),
    folded into a donated device accumulator
    (``ops.logreg_kernel.update_logreg_stats``) — the Hessian's XᵀWX runs
    on the executor's MXU, not its CPU."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.logistic_regression import _check_binary
    from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype
    from spark_rapids_ml_tpu.ops.logreg_kernel import (
        init_logreg_carry,
        update_logreg_stats,
    )

    device = _resolve_device(device_id)
    dt = _resolve_dtype(dtype)
    w = np.asarray(w, dtype=np.float64).reshape(-1)
    n = w.shape[0]
    carry = None
    w_dev = b_dev = None
    loss = 0.0
    rows_seen = 0   # host-exact: the device carry's count lane is f32
    for x, y in _xy_matrices(batches, features_col, label_col):
        m = x.shape[0]
        if m == 0:
            continue
        rows_seen += m
        _check_binary(y)
        if carry is None:
            carry = init_logreg_carry(n, dt, device)
            w_dev = jax.device_put(jnp.asarray(w, dtype=dt), device)
            b_dev = jax.device_put(jnp.asarray(float(b), dtype=dt), device)
        bucket = _bucket_rows(m)
        z = np.concatenate([x, y.reshape(-1, 1)], axis=1)
        if bucket != m:
            padded = np.zeros((bucket, n + 1), dtype=z.dtype)
            padded[:m] = z
            mask = np.zeros(bucket, dtype=bool)
            mask[:m] = True
            carry = update_logreg_stats(
                carry, jnp.asarray(padded, dtype=dt), w_dev, b_dev,
                jnp.asarray(mask),
            )
        else:
            carry = update_logreg_stats(
                carry, jnp.asarray(z, dtype=dt), w_dev, b_dev
            )
        # stable per-row NLL on host (one matvec — a rounding error next
        # to the device XᵀWX): log(1+e^z) − y·z
        zlin = x @ w + float(b)
        loss += float(np.logaddexp(0.0, zlin).sum() - y @ zlin)
    if carry is None:
        return
    carry = jax.block_until_ready(carry)
    gx, hxx, hxb, rsum, ssum, cnt = (
        np.asarray(v, dtype=np.float64) for v in carry
    )
    yield {
        "gx": gx.tolist(),
        "hxx": hxx.ravel().tolist(),
        "hxb": hxb.tolist(),
        "rsum": float(rsum),
        "ssum": float(ssum),
        "loss": loss,
        "count": rows_seen,
    }


def partition_logreg_stats_device_arrow(batches, features_col: str,
                                        label_col: str, w: np.ndarray,
                                        b: float, device_id: int = -1):
    import pyarrow as pa

    from spark_rapids_ml_tpu.spark.aggregate import (
        logreg_stats_arrow_schema,
    )

    for row in partition_logreg_stats_device(
        batches, features_col, label_col, w, b, device_id
    ):
        yield pa.RecordBatch.from_pylist(
            [row], schema=logreg_stats_arrow_schema()
        )


def _kmeans_stats_update_impl(carry, xb, mask, centers):
    """One Lloyd assignment half-step into a donated carry. Per-cluster
    counts ride an int32 lane (f32 would saturate at 2^24 and silently
    bias centers = sums/counts on large partitions); the one-hot matmul
    scatter stays in the compute dtype for the MXU."""
    import jax
    import jax.numpy as jnp

    sums, counts, cost = carry
    k = centers.shape[0]
    d2 = (
        jnp.sum(xb * xb, axis=1)[:, None]
        + jnp.sum(centers * centers, axis=1)[None, :]
        - 2.0 * jax.lax.dot_general(
            xb, centers, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
        )
    )
    d2 = jnp.maximum(d2, 0.0)
    labels = jnp.argmin(d2, axis=1)
    hit = (labels[:, None] == jnp.arange(k)[None, :])
    onehot = hit.astype(xb.dtype) * mask[:, None]
    sums = sums + jax.lax.dot_general(
        onehot, xb, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )
    counts = counts + jnp.sum(
        (hit & (mask[:, None] > 0)).astype(jnp.int32), axis=0
    )
    cost = cost + jnp.sum(jnp.min(d2, axis=1) * mask)
    return sums, counts, cost


_KMEANS_UPDATE = None


def _kmeans_stats_update(carry, xb, mask, centers):
    """Jit-cached (donated-carry) wrapper — one compiled program per
    shape across every partition task and Lloyd iteration."""
    global _KMEANS_UPDATE
    if _KMEANS_UPDATE is None:
        import jax

        _KMEANS_UPDATE = jax.jit(_kmeans_stats_update_impl,
                                 donate_argnums=(0,))
    return _KMEANS_UPDATE(carry, xb, mask, centers)


def partition_kmeans_stats_device(
    batches: Iterable,
    input_col: str,
    centers: np.ndarray,
    device_id: int = -1,
    dtype: str = "auto",
) -> Iterator[Dict[str, object]]:
    """Device counterpart of ``aggregate.partition_kmeans_stats``: one
    Lloyd assignment half-step per partition on the executor's
    accelerator — assignment distances and the per-cluster Σx as MXU
    matmuls (the one-hot-matmul scatter), accumulated in a donated
    carry."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype

    device = _resolve_device(device_id)
    dt = _resolve_dtype(dtype)
    centers = np.asarray(centers, dtype=np.float64)
    k, n = centers.shape

    c_dev = None
    carry = None
    rows_seen = 0   # host-exact: float cluster counts are a result, the
    # partition row count must not ride f32
    for batch in batches:
        if hasattr(batch, "column"):
            x = vector_column_to_matrix(batch.column(input_col))
        else:
            x = np.asarray(batch, dtype=np.float64)
        m = x.shape[0]
        if m == 0:
            continue
        rows_seen += m
        if carry is None:
            c_dev = jax.device_put(jnp.asarray(centers, dtype=dt), device)
            carry = jax.device_put(
                (
                    jnp.zeros((k, n), dtype=dt),
                    jnp.zeros((k,), dtype=jnp.int32),
                    jnp.zeros((), dtype=dt),
                ),
                device,
            )
        bucket = _bucket_rows(m)
        if bucket != m:
            padded = np.zeros((bucket, n), dtype=x.dtype)
            padded[:m] = x
            mask = np.zeros(bucket)
            mask[:m] = 1.0
        else:
            padded = x
            mask = np.ones(m)
        carry = _kmeans_stats_update(
            carry, jnp.asarray(padded, dtype=dt),
            jnp.asarray(mask, dtype=dt), c_dev,
        )
    if carry is None:
        return
    carry = jax.block_until_ready(carry)
    sums, counts, cost = (np.asarray(v, dtype=np.float64) for v in carry)
    yield {
        "sums": sums.ravel().tolist(),
        "counts": counts.tolist(),
        "cost": float(cost),
        "count": rows_seen,
    }


def partition_kmeans_stats_device_arrow(batches, input_col: str,
                                        centers: np.ndarray,
                                        device_id: int = -1):
    import pyarrow as pa

    from spark_rapids_ml_tpu.spark.aggregate import (
        kmeans_stats_arrow_schema,
    )

    for row in partition_kmeans_stats_device(
        batches, input_col, centers, device_id
    ):
        yield pa.RecordBatch.from_pylist(
            [row], schema=kmeans_stats_arrow_schema()
        )


def partition_gram_stats_device_arrow(
    batches, input_col: str, device_id: int = -1, batch_rows: int = 0,
    precision=None,
):
    """``mapInArrow`` adapter for the device path — same output schema as
    the host adapter, so driver combine/finalize code is shared; the row is
    Arrow, made inside the task's hand-back of the moments as fetched."""
    yield from partition_gram_stats_device(
        batches, input_col, device_id, batch_rows=batch_rows,
        precision=precision, row=stats_record_batch)


def _task_identity():
    """(partition_id, num_partitions) of the running barrier task.

    pyspark's ``TaskContext`` when available (real clusters); the local
    engine's exported env otherwise."""
    import os

    try:  # pragma: no cover - pyspark environments
        from pyspark import TaskContext

        ctx = TaskContext.get()
        if ctx is not None:
            return int(ctx.partitionId()), int(ctx.numPartitions())
    except ImportError:
        pass
    pid = os.environ.get("LOCALSPARK_PARTITION_ID")
    n = os.environ.get("LOCALSPARK_NUM_PARTITIONS")
    if pid is None or n is None:
        raise RuntimeError(
            "collective executor aggregation needs barrier task identity "
            "(pyspark TaskContext or the local engine's process executors)"
        )
    return int(pid), int(n)


def partition_gram_stats_device_collective(
    batches,
    input_col: str,
    coordinator: str,
    n_features: int,
    device_id: int = -1,
    dtype: str = "auto",
):
    """Barrier-stage executor aggregation with an ON-DEVICE global reduce.

    The full reference architecture, TPU-native end to end: every barrier
    task streams its partition through its own accelerator's donated
    accumulator (as ``partition_gram_stats_device``), then all tasks join
    one ``jax.distributed`` job (coordinator = the partition-0 host) and
    the partial (Σxxᵀ, Σx, n) are summed by ONE compiled collective over
    the global device mesh — the ``psum`` that replaces the reference's
    executor→driver Spark-RPC reduce of n×n partials
    (``RapidsRowMatrix.scala:202``). Only partition 0 emits the combined
    row; the driver-side ``combine_stats`` sees exactly one row and adds
    nothing.

    Reachability note: the coordinator service binds inside the
    partition-0 task, so ``coordinator`` must be an address the other
    executors can reach — automatic for single-host executor fleets (the
    local engine, one-box Spark); multi-host fleets pre-set
    ``SPARK_RAPIDS_ML_TPU_COORDINATOR`` to a routable host:port.
    """
    import os

    part_id, n_parts = _task_identity()
    os.environ["SPARK_RAPIDS_ML_TPU_COORDINATOR"] = coordinator
    os.environ["SPARK_RAPIDS_ML_TPU_NUM_PROCESSES"] = str(n_parts)
    os.environ["SPARK_RAPIDS_ML_TPU_PROCESS_ID"] = str(part_id)

    from spark_rapids_ml_tpu.parallel.multihost import (
        global_data_mesh,
        initialize_multihost,
        make_global_array,
    )

    joined = initialize_multihost()
    if not joined and n_parts > 1:
        raise RuntimeError(
            "collective aggregation: failed to join the "
            f"{n_parts}-process jax.distributed job at {coordinator}"
        )

    local = list(partition_gram_stats_device(
        batches, input_col, device_id, dtype
    ))
    import numpy as np_

    n = int(n_features)
    if local:
        gram = np_.asarray(local[0]["gram"], dtype=np_.float64)
        col_sum = np_.asarray(local[0]["col_sum"], dtype=np_.float64)
        count = int(local[0]["count"])
        if col_sum.shape[0] != n:
            raise ValueError(
                f"partition feature dim {col_sum.shape[0]} != driver-"
                f"announced {n}"
            )
    else:
        # empty partition still joins the collective with zeros — bailing
        # out here would strand every other barrier task inside the reduce
        gram = np_.zeros(n * n)
        col_sum = np_.zeros(n)
        count = 0

    if n_parts == 1:
        if local:
            yield stats_record_batch(**local[0])
        return

    import jax
    import jax.numpy as jnp

    # one (1, n²+n) float row + one (1, 2) int32 count per process,
    # row-sharded over the global mesh; the jitted sums over the process
    # axis ARE the cross-host collective (XLA lowers them over ICI/DCN),
    # outputs replicated to every process. Floats ride f32 — the device
    # accumulator's own dtype on TPU (x64 is CPU-only). The count rides
    # TWO int32 lanes (hi = count >> 20, lo = count & 0xFFFFF): int64
    # would silently downcast without x64, and a single int32 lane wraps
    # at 2^31 total rows — split lanes stay exact to 2^51 rows for up to
    # ~2k partitions
    mesh = global_data_mesh()
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    packed = np_.concatenate([gram.ravel(), col_sum]).astype(
        np_.float32
    )[None, :]
    counts = np_.asarray(
        [[count >> 20, count & 0xFFFFF]], dtype=np_.int32
    )
    global_rows = make_global_array(packed, mesh, n_parts)
    global_counts = make_global_array(counts, mesh, n_parts)
    total, count_lanes = jax.jit(
        lambda r, c: (jnp.sum(r, axis=0), jnp.sum(c, axis=0)),
        out_shardings=(repl, repl),
    )(global_rows, global_counts)
    total = np_.asarray(total, dtype=np_.float64)
    hi, lo = (int(v) for v in np_.asarray(count_lanes))
    count_total = (hi << 20) + lo
    if part_id != 0:
        return
    yield stats_record_batch(total[: n * n], total[n * n :], count_total)


def partition_multinomial_stats_device(
    batches,
    features_col: str,
    label_col: str,
    classes: np.ndarray,
    wb: np.ndarray,
    device_id: int = -1,
    dtype: str = "auto",
):
    """Device counterpart of ``aggregate.partition_multinomial_stats``:
    the raw softmax partials fold into a donated device accumulator
    (``ops.logreg_kernel.update_multinomial_stats``) — the K² Hessian
    Grams run on the executor's MXU. Loss accumulates on host (one
    (m, K) logits pass — negligible next to the Hessian)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.logistic_regression import (
        class_indices,
        softmax_log_loss,
    )
    from spark_rapids_ml_tpu.models.pca import _resolve_device, _resolve_dtype
    from spark_rapids_ml_tpu.ops.logreg_kernel import update_multinomial_stats

    device = _resolve_device(device_id)
    dt = _resolve_dtype(dtype)
    classes = np.asarray(classes, dtype=np.float64)
    k = classes.size
    wb = np.asarray(wb, dtype=np.float64)
    n = wb.shape[1] - 1
    dim = n + 1
    eye_k = np.eye(k)
    carry = None
    wb_dev = None
    loss = 0.0
    rows_seen = 0
    for x, y in _xy_matrices(batches, features_col, label_col):
        m = x.shape[0]
        if m == 0:
            continue
        idx = class_indices(y, classes)
        rows_seen += m
        if carry is None:
            carry = jax.device_put(
                (
                    jnp.zeros((k, dim), dtype=dt),
                    jnp.zeros((k * dim, k * dim), dtype=dt),
                    jnp.zeros((), dtype=dt),
                ),
                device,
            )
            wb_dev = jax.device_put(jnp.asarray(wb, dtype=dt), device)
        y_oh = eye_k[idx]
        bucket = _bucket_rows(m)
        if bucket != m:
            x_pad = np.zeros((bucket, n), dtype=x.dtype)
            x_pad[:m] = x
            oh_pad = np.zeros((bucket, k))
            oh_pad[:m] = y_oh
            mask = np.zeros(bucket, dtype=bool)
            mask[:m] = True
            carry = update_multinomial_stats(
                carry, jnp.asarray(x_pad, dtype=dt),
                jnp.asarray(oh_pad, dtype=dt), wb_dev, jnp.asarray(mask),
            )
        else:
            carry = update_multinomial_stats(
                carry, jnp.asarray(x, dtype=dt),
                jnp.asarray(y_oh, dtype=dt), wb_dev,
            )
        loss += softmax_log_loss(x, wb, idx)
    if carry is None:
        return
    carry = jax.block_until_ready(carry)
    gxa, h_raw, _ = (np.asarray(v, dtype=np.float64) for v in carry)
    yield {
        "gxa": gxa.ravel().tolist(),
        "h": h_raw.ravel().tolist(),
        "loss": loss,
        "count": rows_seen,
    }


# --------------------------------------------------------------------------
# tree-ensemble histogram partials ON the executor's accelerator
# --------------------------------------------------------------------------

_HIST_RUN = None  # lazily-built jitted histogram program (jax is an
# executor-optional import in this module; the compile cache must outlive
# calls so per-batch invocations reuse the traced program)


def _hist_device_multi(binned, local_nodes, channels, n_nodes, n_bins):
    """(T, C, nodes, d·bins) histograms for a tree GROUP in one compiled
    program: the bin one-hot is built ONCE per batch and every tree's
    node-scatter runs as the same MXU contraction the in-kernel grower
    uses (``ops.forest_kernel._channel_histograms``) — per-partition
    executor compute, exactly where the reference put its per-partition
    GEMM (``RapidsRowMatrix.scala:168-202``)."""
    global _HIST_RUN
    if _HIST_RUN is None:
        import functools

        import jax

        from spark_rapids_ml_tpu.ops.forest_kernel import (
            _bin_onehot,
            _channel_histograms,
        )

        @functools.partial(jax.jit, static_argnames=("nn", "nb"))
        def run(b, nodes, ch, nn, nb):
            bin_oh = _bin_onehot(b, nb, ch.dtype)

            def one(nodes_t, ch_t):
                node_oh = jax.nn.one_hot(nodes_t, nn, dtype=ch_t.dtype)
                return _channel_histograms(node_oh, bin_oh, ch_t)

            return jax.vmap(one)(nodes, ch)

        _HIST_RUN = run
    return _HIST_RUN(binned, local_nodes, channels, n_nodes, n_bins)


def partition_forest_histograms_device(
    batches: Iterable,
    features_col: str,
    label_col: str,
    spec: dict,
    device_id: int = -1,
    dtype: str = "auto",
):
    """Device counterpart of ``forest_plane.partition_forest_histograms``:
    identical spec/row contract (driver combine is shared), but the
    (C, nodes, d, bins) statistics accumulate as jitted MXU contractions
    on this executor's accelerator. Host does the cheap parts (binning,
    partial-tree routing, bootstrap weights); the scatter-heavy histogram
    runs on device. f32 accumulate on accelerators — exact for counts to
    2^24 per partition, then combined in f64 on the driver."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.pca import (
        _resolve_device,
        _resolve_dtype,
    )
    from spark_rapids_ml_tpu.ops.forest_kernel import apply_bin_edges
    from spark_rapids_ml_tpu.spark.forest_plane import (
        _batch_weights,
        _batch_xy,
        _draw_weights,
        _tree_weight_stream,
        partition_identity,
        route_to_level_np,
    )

    edges = np.asarray(spec["edges"])
    n_bins = int(spec["n_bins"])
    level = int(spec["level"])
    rate = float(spec["subsampling_rate"])
    seed = int(spec["seed"])
    classes = spec.get("classes")
    trees = spec["trees"]
    pid = partition_identity()
    n_nodes = 2 ** level
    d = edges.shape[0]
    n_ch = 3 if classes is None else len(classes)
    device = _resolve_device(device_id)
    dt = _resolve_dtype(dtype)

    streams = [
        _tree_weight_stream(rate, seed, int(t["tree"]), pid,
                            always_poisson=True,
                            bootstrap=bool(spec.get("bootstrap", True)))
        for t in trees
    ]
    tree_feats = [np.asarray(t["feature"]) for t in trees]
    tree_thrs = [np.asarray(t["threshold"]) for t in trees]
    acc = None
    for batch in batches:
        x, y = _batch_xy(batch, features_col, label_col)
        m = x.shape[0]
        if m == 0:
            continue
        binned = apply_bin_edges(x, edges)
        bucket = _bucket_rows(m)
        w_user = _batch_weights(batch, spec.get("weight_col"), m)
        if classes is not None:
            y_idx = np.searchsorted(np.asarray(classes), y)
            onehot = np.eye(len(classes))[y_idx]
        nodes_np = np.zeros((len(trees), bucket), dtype=np.int32)
        ch_np = np.zeros((len(trees), bucket, n_ch))
        for ti in range(len(trees)):
            w = _draw_weights(streams[ti], rate, m)
            if w_user is not None:
                w = w * w_user
            if classes is None:
                ch_np[ti, :m] = np.stack([w, w * y, w * y * y], axis=1)
            else:
                ch_np[ti, :m] = onehot * w[:, None]
            nodes_np[ti, :m] = route_to_level_np(
                binned, tree_feats[ti], tree_thrs[ti], level
            )
        binned_p = np.zeros((bucket, d), dtype=np.int32)
        binned_p[:m] = binned
        out = _hist_device_multi(
            jax.device_put(jnp.asarray(binned_p), device),
            jax.device_put(jnp.asarray(nodes_np), device),
            jax.device_put(jnp.asarray(ch_np, dtype=dt), device),
            n_nodes, n_bins,
        )
        acc = out if acc is None else acc + out
    if acc is None:
        return
    acc_np = np.asarray(acc, dtype=np.float64)
    for ti, t in enumerate(trees):
        yield {
            "tree": int(t["tree"]),
            "hist": acc_np[ti].ravel().tolist(),
        }


def partition_gbt_histograms_device(
    batches: Iterable,
    features_col: str,
    label_col: str,
    spec: dict,
    device_id: int = -1,
    dtype: str = "auto",
):
    """Device counterpart of ``forest_plane.partition_gbt_histograms``:
    residuals/margins compute on host from the broadcast prior ensemble,
    the variance-channel histogram contraction runs on this executor's
    accelerator. Same row contract as the host plane."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.pca import (
        _resolve_device,
        _resolve_dtype,
    )
    from spark_rapids_ml_tpu.ops.forest_kernel import apply_bin_edges
    from spark_rapids_ml_tpu.spark.forest_plane import (
        _batch_weights,
        _batch_xy,
        _draw_weights,
        _gbt_margin,
        _gbt_residual_hess,
        _tree_weight_stream,
        partition_identity,
        route_to_level_np,
    )

    edges = np.asarray(spec["edges"])
    n_bins = int(spec["n_bins"])
    level = int(spec["level"])
    depth = int(spec["depth"])
    rate = float(spec["subsampling_rate"])
    seed = int(spec["seed"])
    tree_idx = int(spec["tree"])
    pid = partition_identity()
    n_nodes = 2 ** level
    d = edges.shape[0]
    device = _resolve_device(device_id)
    dt = _resolve_dtype(dtype)

    stream = _tree_weight_stream(rate, seed, tree_idx, pid,
                                 always_poisson=False)
    feature = np.asarray(spec["feature"])
    threshold = np.asarray(spec["threshold"])
    acc = None
    for batch in batches:
        x, y = _batch_xy(batch, features_col, label_col)
        m = x.shape[0]
        if m == 0:
            continue
        binned = apply_bin_edges(x, edges)
        f = _gbt_margin(
            binned, spec.get("ens_feature"), spec.get("ens_threshold"),
            spec.get("ens_leaf"), spec["init"], spec["step_size"], depth,
        )
        r, _ = _gbt_residual_hess(y, f, bool(spec["classification"]))
        w = _draw_weights(stream, rate, m)
        w_user = _batch_weights(batch, spec.get("weight_col"), m)
        if w_user is not None:
            w = w * w_user
        bucket = _bucket_rows(m)
        ch_np = np.zeros((1, bucket, 3))
        ch_np[0, :m] = np.stack([w, w * r, w * r * r], axis=1)
        nodes_np = np.zeros((1, bucket), dtype=np.int32)
        nodes_np[0, :m] = route_to_level_np(binned, feature, threshold,
                                            level)
        binned_p = np.zeros((bucket, d), dtype=np.int32)
        binned_p[:m] = binned
        out = _hist_device_multi(
            jax.device_put(jnp.asarray(binned_p), device),
            jax.device_put(jnp.asarray(nodes_np), device),
            jax.device_put(jnp.asarray(ch_np, dtype=dt), device),
            n_nodes, n_bins,
        )
        acc = out if acc is None else acc + out
    if acc is None:
        return
    yield {
        "tree": tree_idx,
        "hist": np.asarray(acc[0], dtype=np.float64).ravel().tolist(),
    }
