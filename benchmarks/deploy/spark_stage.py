"""Spark's stage, stood in for: ``spark.PCA(...).fit(frame)`` — the front's
own entry — over host partitions.

A PySpark job calls ``spark_rapids_ml_tpu.spark.PCA(k=…, inputCol=…)
.fit(df)``. The front selects the input column, maps an executor function
over the frame's partitions with ``DataFrame.mapInArrow`` (one task a
partition, each handed a one-shot iterator of ``pyarrow.RecordBatch``es of
``spark.sql.execution.arrow.maxRecordsPerBatch`` rows and yielding one
statistics row), collects the rows on the driver, merges them and solves.
There is no ``pyspark`` here and the harness makes host partitions as NumPy
chunks (``run.dataset_factory``), so this file holds what stands between
the two and nothing more:

* ``ColumnarFrame``: the pyspark ``DataFrame`` surface ``PCA._fit`` calls —
  ``select``, ``mapInArrow(fn, ddl)`` and, on what that returns,
  ``toArrow()`` (pyspark >= 4.0) and ``collect()``. One host chunk is one
  partition is one task; a task's rows arrive as ``list<float>`` record
  batches of ``recordBatchRows`` rows and a ragged rest, zero-copy views of
  the chunk (``deploy/arrow_partition.record_batches``). Tasks run one
  after the other in this process: one chip an executor, one task at a
  time, the Python worker reused — what
  ``spark.task.resource.<chip>.amount = 1`` gives. What a task yields
  crosses an Arrow IPC round trip (a stream written into a buffer and
  opened again) before the driver half sees it, as executor -> JVM ->
  driver would: the row's bytes are copied, no Python object is made. The
  frame sums the seconds of those round trips.
* ``SparkStagePCA``: the configuration's estimator. It keeps two Params of
  its own (``recordBatchRows``, ``arrowColumn``), sets every other on the
  front's ``PCA`` at once (a front without that Param refuses it there),
  and ``fit`` returns what the harness reads — ``pc`` (n x k),
  ``explained_variance``, ``mean``, ``fit_timings_``, ``svd_solver_used_``
  — read off the front's model, the round trips' seconds added to the
  timings as ``stage/collect``.

It imports nothing of the program but ``spark_rapids_ml_tpu.spark``.
"""

from __future__ import annotations

import time

from benchmarks.deploy.arrow_partition import record_batches

OWN_PARAMS = ("recordBatchRows", "arrowColumn")
COLLECT_PHASE = "stage/collect"  # benchmarks/work/stage.py: COLLECT_PHASE


def ipc_round_trip(batch):
    """``batch`` written as an Arrow IPC stream into a buffer and read back
    from it: the same values in other memory, as a row that left its
    process would arrive."""
    import pyarrow as pa

    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, batch.schema) as writer:
        writer.write_batch(batch)
    return pa.ipc.open_stream(sink.getvalue()).read_next_batch()


class ColumnarFrame:
    """Host partitions behind the ``DataFrame`` surface ``PCA._fit`` calls
    (see the module's text)."""

    def __init__(self, partitions, batch_rows: int, column: str):
        self.partitions = list(partitions)
        self.batch_rows = int(batch_rows)
        self.column = column
        self.collect_seconds = 0.0  # the IPC round trips of every stage

    def select(self, *columns):
        if list(columns) != [self.column]:
            raise KeyError(f"the frame has the column {self.column!r}, "
                           f"not {columns!r}")
        return self

    def mapInArrow(self, fn, schema):
        return _MappedStage(self, fn)


class _MappedStage:
    """``mapInArrow``'s lazy result: ``toArrow()`` or ``collect()`` runs
    the stage, one task a partition."""

    def __init__(self, frame: ColumnarFrame, fn):
        self.frame = frame
        self.fn = fn

    def _run(self) -> list:
        frame, out = self.frame, []
        for chunk in frame.partitions:
            task = self.fn(record_batches([chunk], frame.batch_rows,
                                          frame.column))
            for batch in task:
                t0 = time.perf_counter()
                out.append(ipc_round_trip(batch))
                frame.collect_seconds += time.perf_counter() - t0
        return out

    def toArrow(self):
        import pyarrow as pa

        batches = self._run()
        return pa.Table.from_batches(batches) if batches else pa.table({})

    def collect(self) -> list:
        """The rows as dicts of Python values: what ``collect()`` of
        ``Row``s costs (an n x n Gram is n squared float objects)."""
        return [row for batch in self._run() for row in batch.to_pylist()]


class StageFit:
    """What the harness reads of a fit, off the front's model."""

    def __init__(self, model, collect_seconds: float):
        self.model = model
        self.pc = model.pc.toArray()
        self.explained_variance = model.explainedVariance.toArray()
        self.mean = model.mean.toArray()
        self.fit_timings_ = {**(getattr(model, "fit_timings_", None) or {}),
                             COLLECT_PHASE: collect_seconds}
        self.svd_solver_used_ = getattr(model, "svd_solver_used_", None)
        self.fit_report_ = getattr(model, "fit_report_", None)


class SparkStagePCA:
    """The front's ``PCA`` behind Spark's stage (see the module's text)."""

    def __init__(self):
        from spark_rapids_ml_tpu import spark

        self.front = spark.PCA()
        self.own = {}

    def set(self, name: str, value):
        if name in OWN_PARAMS:
            self.own[name] = value
        else:
            self.front._set(**{name: value})
        return self

    def fit(self, dataset) -> StageFit:
        """``dataset``: an iterator of NumPy chunks, or a zero-argument
        callable returning them; each chunk is one partition."""
        column = self.own["arrowColumn"]
        self.front._set(inputCol=column)
        frame = ColumnarFrame(dataset() if callable(dataset) else dataset,
                              self.own["recordBatchRows"], column)
        return StageFit(self.front.fit(frame), frame.collect_seconds)
