"""Spark's Arrow hand-over, stood in for: ``PCA.fit`` fed a partition as
record batches.

``DataFrame.mapInArrow`` hands a Python worker a one-shot iterator of
``pyarrow.RecordBatch``es of ``spark.sql.execution.arrow.maxRecordsPerBatch``
rows (default 10,000), the features in one ``array<float>`` column. The
harness makes host partitions as NumPy chunks (``run.dataset_factory``), so
this estimator stands between the two: it keeps two Params of its own
(``recordBatchRows``, ``arrowColumn``), forwards every other to the
program's ``PCA``, and hands ``PCA.fit`` the chunks re-dressed as record
batches — ``recordBatchRows`` rows each and a partition's ragged rest, as
zero-copy views of the chunk: the chunk's buffer as the column's values,
one int32 offsets array, slices of the one list array. It copies no row
byte and imports nothing of the program but ``PCA``; the model is
``PCA.fit``'s, untouched.
"""

from __future__ import annotations

import numpy as np

OWN_PARAMS = ("recordBatchRows", "arrowColumn")


def record_batches(chunks, batch_rows: int, column: str):
    """The host chunks as ``list<float>`` record batches of ``batch_rows``
    rows (a chunk's last one holds what is left), each a view of its
    chunk."""
    import pyarrow as pa

    for chunk in chunks:
        m, n = chunk.shape
        values = pa.array(chunk.reshape(-1))  # wraps the buffer, no copy
        offsets = pa.array(np.arange(0, (m + 1) * n, n, dtype=np.int32))
        rows = pa.ListArray.from_arrays(offsets, values)
        for start in range(0, m, batch_rows):
            yield pa.RecordBatch.from_arrays(
                [rows.slice(start, batch_rows)], names=[column])


class ArrowFedPCA:
    """``PCA`` behind Spark's Arrow stream (see the module's text)."""

    def __init__(self):
        from spark_rapids_ml_tpu.models.pca import PCA

        self.pca = PCA()
        self.own = {}

    def set(self, name: str, value):
        if name in OWN_PARAMS:
            self.own[name] = value
        else:
            self.pca.set(name, value)
        return self

    def fit(self, dataset):
        """``dataset``: an iterator of NumPy chunks (handed on as a
        one-shot generator of record batches) or a zero-argument callable
        returning the chunks (handed on as a callable returning a fresh
        generator, so the source stays re-iterable)."""
        batch_rows = int(self.own["recordBatchRows"])
        column = self.own["arrowColumn"]
        self.pca.set("inputCol", column)
        if callable(dataset):
            return self.pca.fit(
                lambda: record_batches(dataset(), batch_rows, column))
        return self.pca.fit(record_batches(dataset, batch_rows, column))
