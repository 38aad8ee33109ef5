"""Columnar chunks through the one streamed loop (CPU, small sizes).

``PCA.fit`` over Arrow record batches — what Spark's ``mapInArrow`` hands a
Python worker — must be the fit over the same rows as NumPy chunks, bit for
bit: ``data/arrow.py`` reads a vector column as a view, ``data/batches.py``
re-blocks the chunks whatever their sizes, and everything after that is the
loop every other input takes. The spans and counters the source reports
inside ``stream:next`` are held here against the record-batch count and
against the benchmark's names (``benchmarks/work/reblock.py``).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data import arrow as arrow_reader
from spark_rapids_ml_tpu.data.batches import SOURCE_COUNTERS, BatchSource
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.spark import aggregate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, BATCH, K = 24, 128, 3
ROWS = (300, 212)      # two "partitions": 512 rows = four device batches
RECORD_ROWS = 70       # 4 x 70 + 20 and 3 x 70 + 2: nine record batches
COLUMN = "features"

VECTOR_UDT = pa.struct([("type", pa.int8()), ("size", pa.int32()),
                        ("indices", pa.list_(pa.int32())),
                        ("values", pa.list_(pa.float64()))])


def _bench_module(relpath: str):
    path = os.path.join(ROOT, "benchmarks", relpath)
    spec = importlib.util.spec_from_file_location(
        "arrow_test_" + relpath.replace("/", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _chunks(dtype=np.float32, rows=ROWS, n=N, seed=11) -> list:
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(r, n)) + 0.5).astype(dtype) for r in rows]


def _lists(chunk, offsets_type=np.int32, large=False):
    m, n = chunk.shape
    offsets = pa.array(np.arange(0, (m + 1) * n, n, dtype=offsets_type))
    maker = pa.LargeListArray if large else pa.ListArray
    return maker.from_arrays(offsets, pa.array(chunk.reshape(-1)))


def _dense_structs(chunk):
    m = chunk.shape[0]
    return pa.StructArray.from_arrays(
        [pa.array(np.ones(m, dtype=np.int8)), pa.nulls(m, pa.int32()),
         pa.nulls(m, pa.list_(pa.int32())), _lists(chunk)],
        fields=list(VECTOR_UDT))


# kind -> (dtype of the rows, chunk -> one Arrow array of all its rows)
KINDS = {
    "list": (np.float32, _lists),
    "large_list": (np.float32,
                   lambda c: _lists(c, offsets_type=np.int64, large=True)),
    "fixed_size_list": (np.float32, lambda c: pa.FixedSizeListArray
                        .from_arrays(pa.array(c.reshape(-1)), c.shape[1])),
    "float64_list": (np.float64, _lists),
    "vector_udt_dense": (np.float64, _dense_structs),
}


def _record_batches(chunks, kind="list", rows=RECORD_ROWS, column=COLUMN):
    """Each chunk as record batches of ``rows`` rows and its ragged rest:
    slices of one array, so all but the first have a non-zero offset."""
    make = KINDS[kind][1]
    for chunk in chunks:
        whole = make(chunk)
        for start in range(0, chunk.shape[0], rows):
            yield pa.RecordBatch.from_arrays([whole.slice(start, rows)],
                                             names=[column])


def _estimator():
    return PCA().setK(K).set("batchRows", BATCH).set("dtype", "float32")


def _same_model(a, b) -> bool:
    return (np.array_equal(a.pc, b.pc) and np.array_equal(a.mean, b.mean)
            and np.array_equal(a.explained_variance, b.explained_variance))


# -- bit-equality with the same rows as NumPy chunks --------------------------


@pytest.mark.parametrize("form", ["iterator", "callable"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_fit_over_record_batches_is_the_fit_over_numpy_chunks(kind, form):
    chunks = _chunks(KINDS[kind][0])
    if form == "iterator":
        arrow = _estimator().fit(_record_batches(chunks, kind))
        plain = _estimator().fit(iter(chunks))
    else:
        arrow = _estimator().fit(lambda: _record_batches(chunks, kind))
        plain = _estimator().fit(lambda: list(chunks))
    assert _same_model(arrow, plain)
    ingest = arrow.fit_report_.extra["ingest"]
    assert ingest["passes"] == (1 if form == "iterator" else 2)
    assert ingest["chunks_copied"] == 0
    assert arrow.fit_report_.rows == sum(ROWS)


def _table(chunks):
    return pa.Table.from_batches(list(_record_batches(chunks)))


def _reader(chunks):
    batches = list(_record_batches(chunks))
    return pa.RecordBatchReader.from_batches(batches[0].schema, batches)


def _with_tiny_batches(chunks):
    """A 0-row batch first (no width to learn from it), then a 1-row one,
    then the rest, with another 0-row batch in the middle."""
    whole = _lists(np.concatenate(chunks))
    cuts = [(0, 0), (0, 1), (1, 200), (201, 0), (201, sum(ROWS) - 201)]
    return iter([pa.RecordBatch.from_arrays([whole.slice(a, n)],
                                            names=[COLUMN])
                 for a, n in cuts])


def _one_batch(chunks):
    return pa.RecordBatch.from_arrays([_lists(np.concatenate(chunks))],
                                      names=[COLUMN])


CARRIERS = {
    # name -> (what fit is handed, passes it takes)
    "table": (_table, 2),
    "record_batch_reader": (_reader, 1),
    "one_and_zero_row_batches": (_with_tiny_batches, 1),
    "one_record_batch": (_one_batch, 2),
}


@pytest.mark.parametrize("carrier", sorted(CARRIERS))
def test_every_carrier_of_record_batches_fits_alike(carrier):
    make, passes = CARRIERS[carrier]
    chunks = _chunks()
    arrow = _estimator().fit(make(chunks))
    plain = _estimator().fit(iter(chunks) if passes == 1
                             else (lambda: list(chunks)))
    assert _same_model(arrow, plain)
    assert arrow.fit_report_.extra["ingest"]["passes"] == passes
    assert arrow.fit_report_.rows == sum(ROWS)


def test_input_col_names_the_column_among_several():
    chunks = _chunks()
    batches = [pa.RecordBatch.from_arrays(
        [pa.array(np.arange(b.num_rows)), b.column(0)], names=["id", "vec"])
        for b in _record_batches(chunks)]
    arrow = _estimator().setInputCol("vec").fit(iter(batches))
    assert _same_model(arrow, _estimator().fit(iter(chunks)))


# -- against the plain reference, under the cell's own limits -----------------


@pytest.mark.parametrize("form", ["iterator", "callable", "callable_sorted"])
def test_arrow_fed_fit_agrees_with_the_plain_reference(form):
    """``benchmarks/reference/pca.py`` on the same host rows, under the
    limits of ``pca4096-fit-arrow10k``, at the benchmark tests' CPU size.
    The benchmark's rows accept the two-pass fit's shifted Gram as they
    come (one walk of the record batches); sorted by a feature they refuse
    it and are read a second time."""
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "pca4096-fit-arrow10k.json")) as f:
        limits = json.load(f)["limits"]
    rows = _bench_module("rows.py")
    reference = _bench_module("reference/pca.py")
    chunks = rows.make_chunks(
        2 ** 31 + 34, 1024, 8192, 2,
        {"spectrum_power": 0.5, "mean_scale": 0.1, "row_scale_sigma": 1.0})
    if form == "callable_sorted":
        x = np.concatenate(chunks)
        x = x[np.argsort(x[:, 0])]
        chunks = [x[:8192], x[8192:]]
    est = PCA().setK(64).set("batchRows", 4096).set("dtype", "float32")
    if form == "iterator":
        fitted = est.fit(_record_batches(chunks, rows=1000))
    else:
        fitted = est.fit(lambda: _record_batches(chunks, rows=1000))
    model = {"pc": fitted.pc, "mean": fitted.mean,
             "explained_variance": fitted.explained_variance}
    correct, compared = reference.compare(
        [model], reference.reference(chunks), limits)
    assert correct, compared
    assert all(c["value"] < 0.5 * c["limit"] for c in compared.values())
    ingest = fitted.fit_report_.extra["ingest"]
    assert ingest["passes"] == (2 if form == "callable_sorted" else 1)
    if form != "iterator":
        assert ingest["gram_shift"]["accepted"] == (form == "callable")
    assert ingest["chunks"] == 18 * ingest["passes"]


# -- what must raise ----------------------------------------------------------


def _null_row():
    return pa.array([[1.0, 2.0], None, [3.0, 4.0]], pa.list_(pa.float32()))


def _null_value():
    return pa.array([[1.0, None], [3.0, 4.0]], pa.list_(pa.float32()))


def _ragged():
    return pa.array([[1.0, 2.0], [3.0], [4.0, 5.0, 6.0]],
                    pa.list_(pa.float32()))


def _null_struct():
    rows = [{"type": 1, "size": None, "indices": None, "values": [1.0, 2.0]},
            None]
    return pa.array(rows, VECTOR_UDT)


@pytest.mark.parametrize("column", [_null_row, _null_value, _ragged,
                                    _null_struct],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_null_or_a_ragged_row_raises(column):
    batch = pa.RecordBatch.from_arrays([column()], names=[COLUMN])
    with pytest.raises(ValueError, match="null|differ in length"):
        arrow_reader.column_to_matrix(batch)
    with pytest.raises(ValueError, match="null|differ in length"):
        PCA().setK(1).fit(iter([batch]))


def test_a_wrong_input_col_raises():
    batches = list(_record_batches(_chunks()))
    with pytest.raises(KeyError, match="nothing_here"):
        _estimator().setInputCol("nothing_here").fit(iter(batches))


def test_several_columns_and_no_input_col_raise():
    batch = next(_record_batches(_chunks()))
    two = pa.RecordBatch.from_arrays(
        [batch.column(0), batch.column(0)], names=["a", "b"])
    with pytest.raises(ValueError, match="inputCol"):
        _estimator().fit(iter([two]))


# -- sparse and mixed rows: the row loop, counted as copied -------------------


def _struct_rows(x, sparse_every: int):
    rows = []
    for i, r in enumerate(x):
        if i % sparse_every == 0:
            idx = np.flatnonzero(r)
            rows.append({"type": 0, "size": len(r),
                         "indices": idx.tolist(), "values": r[idx].tolist()})
        else:
            rows.append({"type": 1, "size": None, "indices": None,
                         "values": r.tolist()})
    return pa.array(rows, VECTOR_UDT)


@pytest.mark.parametrize("sparse_every", [1, 3], ids=["sparse", "mixed"])
def test_sparse_and_mixed_rows_take_the_row_loop(sparse_every):
    chunks = _chunks(np.float64, rows=(60, 40))
    for c in chunks:
        c[:, ::4] = 0.0  # something for a sparse row to leave out
    batches = [pa.RecordBatch.from_arrays(
        [_struct_rows(c, sparse_every)], names=[COLUMN]) for c in chunks]
    assert arrow_reader.column_view(batches[0].column(0)) is None
    est = PCA().setK(K).set("batchRows", 50).set("dtype", "float32")
    arrow = est.fit(iter(batches))
    assert _same_model(arrow, est.fit(iter(chunks)))
    ingest = arrow.fit_report_.extra["ingest"]
    assert (ingest["chunks_copied"], ingest["chunks_viewed"]) == (2, 0)


# -- the reader's result is a view of the Arrow buffer ------------------------


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_readers_result_shares_memory_with_the_arrow_buffer(kind):
    chunk = _chunks(KINDS[kind][0])[0]
    batches = list(_record_batches([chunk], kind))
    got = [arrow_reader.column_to_matrix(b, COLUMN) for b in batches]
    assert np.array_equal(np.concatenate(got), chunk)
    for view in got:
        assert not view.flags.owndata and not view.flags.writeable
        # the record batches are slices of one array whose values are the
        # chunk's own buffer: a view of them is a view of the chunk
        assert np.shares_memory(view, chunk)
    assert got[1].ctypes.data - got[0].ctypes.data == (
        RECORD_ROWS * N * chunk.itemsize)


def test_a_table_column_in_several_chunks_is_joined_once():
    chunks = _chunks()
    table = _table(chunks)
    assert table.column(COLUMN).num_chunks == 9
    got = arrow_reader.column_to_matrix(table, COLUMN)
    assert np.array_equal(got, np.concatenate(chunks))
    assert got.flags.owndata  # a new array: counted as copied by the source


# -- spans, keys and counters -------------------------------------------------


def _span_counts(model) -> dict:
    counts = {}
    for e in obs_spans.get_recorder().events(model.fit_report_.trace_id):
        counts[e.name] = counts.get(e.name, 0) + 1
    return counts


def test_the_source_reports_its_reads_and_copies(monkeypatch):
    # a pool no earlier fit of this process has left a buffer in
    monkeypatch.setattr(streaming, "STAGING", streaming.StagingPool())
    chunks = _chunks()
    model = _estimator().fit(_record_batches(chunks))
    ingest = model.fit_report_.extra["ingest"]
    assert {k: ingest[k] for k in SOURCE_COUNTERS} == {
        "chunks": 9, "chunk_rows_min": 2, "chunk_rows_max": RECORD_ROWS,
        "chunks_viewed": 9, "chunks_copied": 0,
        # 512 rows are four device batches, every one of them assembled
        # across record-batch boundaries, none padded
        "batches_viewed": 0, "batches_copied": 4,
        "bytes_reblocked": 4 * BATCH * N * 4,
        # each written into an array made for it: the CPU keeps the host
        # array it is handed (``streaming.put_copies``), so none comes back
        # to be lent again (``test_reblock_staging.py`` stands in for the
        # chip, where they do)
        "staging_reused": 0, "staging_fresh": 4}
    assert ingest["batches"] == 4 and ingest["bytes_put"] == 4 * BATCH * N * 4
    assert ingest["accumulate_calls"]["xla"] == 4
    spans = _span_counts(model)
    read, copy = (streaming.SPAN_NEXT_PART[p] for p in ("read", "copy"))
    # the first batch was read before the stream started (the width); the
    # pull that finds the iterator exhausted is a read span too
    assert spans[read] == 9 - 1 + 1
    assert spans[copy] == 4
    assert spans[streaming.SPAN_NEXT] == 4 + 1
    t = model.fit_timings_
    r, c = (streaming.PHASE_NEXT_PART[p] for p in ("read", "copy"))
    assert 0 < t[r] and 0 < t[c] and t[r] + t[c] <= t["covariance/next"]


@pytest.mark.parametrize("form", ["iterator", "callable", "matrix"])
def test_aligned_numpy_chunks_are_not_reblocked(form):
    chunks = _chunks(rows=(256, 256))
    dataset = {"iterator": lambda: iter(chunks),
               "callable": lambda: (lambda: list(chunks)),
               "matrix": lambda: np.concatenate(chunks)}[form]()
    model = _estimator().fit(dataset)
    ingest = model.fit_report_.extra["ingest"]
    passes = 1 if form == "iterator" else 2
    assert ingest["bytes_reblocked"] == 0 and ingest["batches_copied"] == 0
    assert ingest["batches_viewed"] == 4 * passes
    assert ingest["chunks_copied"] == 0
    assert ingest["chunks"] == (1 if form == "matrix" else 2) * passes
    # the keys are there, at zero, for the benchmark's readers
    assert model.fit_timings_[streaming.PHASE_NEXT_PART["copy"]] == 0.0
    assert streaming.SPAN_NEXT_PART["copy"] not in _span_counts(model)


def test_a_padded_tail_is_a_copy_and_a_list_chunk_is_a_copied_chunk():
    rows = [list(map(float, range(N)))] * 40
    source = BatchSource(iter([rows, np.ones((30, N))]), batch_rows=64)
    ingest = streaming.IngestTrace()
    streaming.stream_covariance(source, ingest=ingest)
    c = ingest.counters
    assert (c["chunks"], c["chunks_copied"], c["chunks_viewed"]) == (2, 1, 1)
    assert (c["batches_viewed"], c["batches_copied"]) == (0, 2)
    # 64 rows joined from both chunks; then 6 rows into the padded tail
    assert c["bytes_reblocked"] == (64 + 6) * N * 8


def test_untraced_walks_count_nowhere():
    source = BatchSource(lambda: iter(_chunks()), batch_rows=BATCH)
    assert source.trace is None
    pool = [id(b) for b in streaming.STAGING.free()]
    batches = [b for b, _ in source.batches()]
    assert sum(b.shape[0] for b in batches) == 4 * BATCH
    assert source.trace is None
    # every copied batch in an array of its own, the fits' pool not asked
    assert not any(np.shares_memory(a, b) for i, a in enumerate(batches)
                   for b in batches[:i])
    assert [id(b) for b in streaming.STAGING.free()] == pool


def test_the_new_names_are_the_benchmarks_and_not_in_the_stream_list():
    reblock = _bench_module("work/reblock.py")
    bench_spans = _bench_module("work/spans.py")
    assert reblock.SPANS == streaming.SPAN_NEXT_PART
    assert reblock.PHASES == streaming.PHASE_NEXT_PART
    assert reblock.NEXT_SPAN == streaming.SPAN_NEXT
    assert reblock.NEXT_PHASE == streaming.PHASE_NEXT
    new = set(streaming.SPAN_NEXT_PART.values())
    assert not new & set(streaming.STREAM_SPANS)
    # idle seconds under them fall to the enclosing stream:next
    assert not new & set(bench_spans.PROGRAM_SPANS)
    assert all(s.startswith(streaming.SPAN_NEXT + "/") for s in new)
    assert all(p.startswith(streaming.PHASE_NEXT + "/")
               for p in streaming.PHASE_NEXT_PART.values())


# -- the Spark front's densifier ----------------------------------------------


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_spark_front_reads_a_dense_column_without_the_row_loop(monkeypatch,
                                                               kind):
    def no_loop(*a, **k):
        raise AssertionError("a dense column went through the row loop")

    monkeypatch.setattr(aggregate, "densify_vector_rows", no_loop)
    chunk = _chunks(KINDS[kind][0])[0]
    batch = list(_record_batches([chunk], kind))[1]  # a sliced one
    got = aggregate.vector_column_to_matrix(batch.column(0))
    assert got.dtype == np.float64 and got.flags.writeable
    assert np.array_equal(got, chunk[RECORD_ROWS:2 * RECORD_ROWS])


def test_spark_front_keeps_the_row_loop_for_sparse_and_pylist_rows():
    x = _chunks(np.float64, rows=(12,))[0]
    x[:, ::3] = 0.0
    assert np.array_equal(
        aggregate.vector_column_to_matrix(_struct_rows(x, 2)), x)
    assert np.array_equal(
        aggregate.vector_column_to_matrix([r.tolist() for r in x]), x)
    empty = aggregate.vector_column_to_matrix(
        pa.array([], pa.list_(pa.float32())), n_features=7)
    assert empty.shape == (0, 7)


# -- nothing at package import ------------------------------------------------


def test_the_reader_imports_pyarrow_only_when_called():
    code = ("import sys; import spark_rapids_ml_tpu.data.arrow, "
            "spark_rapids_ml_tpu.data.batches, spark_rapids_ml_tpu.models.pca;"
            " print('pyarrow' in sys.modules, 'pandas' in sys.modules)")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr
    assert done.stdout.split() == ["False", "False"]
