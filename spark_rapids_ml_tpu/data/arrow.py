"""Columnar (Arrow) chunks → row matrices: the one reader of what PySpark
hands a Python worker.

``DataFrame.mapInArrow`` gives ``fit`` a one-shot iterator of
``pyarrow.RecordBatch``es of ``spark.sql.execution.arrow.maxRecordsPerBatch``
rows (default 10,000), the features in one column: an ``array<float|double>``
(Arrow ``list`` / ``large_list`` / ``fixed_size_list``) or a ``VectorUDT``
struct. A column of equal-length rows without nulls already *is* the row
matrix, row-major in its child values buffer, so it is read as a view: no
row byte moves here. Sparse or mixed ``VectorUDT`` rows and any other
column type are densified row by row (``densify_vector_rows``, float64)
into a new array. A null or a
row of another length raises; nothing is padded or dropped. The dtype is
the column's: the cast to the fit's dtype stays where it is for every
input (``ops.streaming.IngestTrace.put``).

``pyarrow`` is imported inside the functions: nothing here costs a process
that is never handed a columnar chunk.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from spark_rapids_ml_tpu.data.vector import rows_to_matrix

_VECTOR_UDT_FIELDS = ("type", "size", "indices", "values")
# Spark VectorUDT struct tags (pyspark.ml.linalg.VectorUDT.serialize)
_SPARSE, _DENSE = 0, 1


def is_columnar(chunk) -> bool:
    """A ``pyarrow.RecordBatch`` or ``Table`` (duck-typed: no import)."""
    return hasattr(chunk, "schema") and hasattr(chunk, "column")


def _list_view(col) -> Optional[np.ndarray]:
    """The (m, n) view of one list-typed Arrow array, or None when its
    values are not float32/float64 or it has no row to take a width from."""
    import pyarrow as pa

    t = col.type
    if not (pa.types.is_float32(t.value_type)
            or pa.types.is_float64(t.value_type)):
        return None
    m = len(col)
    # flatten() honours a sliced array's offset; its values stay in place
    values = col.flatten()
    if col.null_count or values.null_count:
        raise ValueError("null in the vector column: a row or a value of a "
                         "row is missing")
    flat = values.to_numpy(zero_copy_only=True)
    if pa.types.is_fixed_size_list(t):
        return flat.reshape(m, t.list_size)
    if m == 0:
        return None  # no row says how wide the rows are
    offsets = col.offsets.to_numpy(zero_copy_only=True)
    n = int(offsets[1] - offsets[0])
    if not np.array_equal(offsets, offsets[0] + n * np.arange(
            m + 1, dtype=offsets.dtype)):
        raise ValueError(f"rows of the vector column differ in length "
                         f"(the first has {n} values)")
    return flat.reshape(m, n)


def column_view(col) -> Optional[np.ndarray]:
    """The (m, n) matrix of one Arrow array as a view of its values buffer:
    a ``list`` / ``large_list`` / ``fixed_size_list`` of float32 or float64,
    or a ``VectorUDT`` struct whose rows are all dense. None where no view
    can be had (sparse or mixed rows, another type, a ``ChunkedArray`` of
    several chunks); a null or a ragged row raises."""
    import pyarrow as pa

    if isinstance(col, pa.ChunkedArray):
        if col.num_chunks != 1:
            return None
        col = col.chunk(0)
    t = col.type
    if pa.types.is_struct(t):
        if tuple(f.name for f in t) != _VECTOR_UDT_FIELDS:
            return None
        if col.null_count:
            raise ValueError("null vector row in input column")
        kinds = col.field("type").to_numpy(zero_copy_only=False)
        if not (kinds == _DENSE).all():
            return None
        col, t = col.field("values"), t.field("values").type
    if (pa.types.is_list(t) or pa.types.is_large_list(t)
            or pa.types.is_fixed_size_list(t)):
        return _list_view(col)
    return None


def densify_vector_rows(column, n_features: Optional[int] = None) -> np.ndarray:
    """A pylist of vector rows as an (m, n) float64 matrix, row by row:
    dense ``VectorUDT`` structs (type=1: values), sparse ones (type=0: size,
    indices, values), plain list rows, and mixed encodings."""
    rows = []
    for entry in column:
        if entry is None:
            raise ValueError("null vector row in input column")
        if isinstance(entry, dict):
            if entry.get("type") == _DENSE or (
                entry.get("type") is None and entry.get("indices") is None
            ):
                rows.append(np.asarray(entry["values"], dtype=np.float64))
            elif entry.get("type") == _SPARSE:
                size = int(entry["size"])
                dense = np.zeros(size)
                idx = np.asarray(entry["indices"], dtype=np.int64)
                dense[idx] = np.asarray(entry["values"], dtype=np.float64)
                rows.append(dense)
            else:
                raise ValueError(f"unrecognized vector struct: {entry!r}")
        else:
            rows.append(np.asarray(entry, dtype=np.float64).reshape(-1))
    if not rows:
        return np.zeros((0, n_features or 0))
    return rows_to_matrix(rows)


def array_to_matrix(col) -> np.ndarray:
    """One Arrow array as an (m, n) matrix: its view where ``column_view``
    has one; the views of a ``ChunkedArray``'s chunks joined in a new
    array; else a new float64 array filled row by row."""
    view = column_view(col)
    if view is not None:
        return view
    if getattr(col, "num_chunks", 0) > 1:
        return np.concatenate([array_to_matrix(c) for c in col.chunks])
    return densify_vector_rows(col.to_pylist())


def column_to_matrix(chunk, column: Optional[str] = None) -> np.ndarray:
    """One vector column of a ``RecordBatch`` or ``Table`` as an (m, n)
    matrix (``array_to_matrix``). ``column`` None = the chunk's only
    column; a name the chunk does not have raises ``KeyError``."""
    if column is None:
        names = chunk.schema.names
        if len(names) != 1:
            raise ValueError(
                f"columnar chunk has columns {names}: name the vector "
                f"column (inputCol)")
        column = names[0]
    return array_to_matrix(chunk.column(column))
