"""The Spark front's own fit — ``spark.PCA(...).fit(frame)`` — as a stage of
executor tasks on the one streamed loop, on the CPU at small widths.

The frame is the benchmark's columnar stand-in for the pyspark surface
``PCA._fit`` calls (``benchmarks/deploy/spark_stage.py``): one NumPy chunk a
partition, one task a partition, each fed ragged record batches, each
statistics row through an Arrow IPC round trip. Held here: the model
against a plain float64 NumPy two-pass PCA and against the in-process
``models.pca.PCA.fit`` of the same rows; an empty partition; a failing
task; the statistics row as a buffer of its own, the float64 image of the
moments as fetched; the sum and the solve's operand written once, into
memory out of Arrow's pool, and the model to the last bit what the merge
of PR 36 (kept below as the plain reference) returned; the one loop's
counters in the front's report; the one gated solve; ``batchRows``
reaching the executor;
the stage's spans and ``fit_timings_`` keys, and their names against the
benchmark's (``benchmarks/work/stage.py``). The suite runs in float64
(``conftest.py``), so the tolerances below are float64's.
"""

from __future__ import annotations

import gc
import importlib.util
import os

import numpy as np
import pyarrow as pa
import pytest

from test_arrow_ingest import _dense_structs

from benchmarks.deploy import spark_stage
from spark_rapids_ml_tpu.models.pca import PCA as LocalPCA
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.spark import aggregate, device_aggregate
from spark_rapids_ml_tpu.spark import estimator as front

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, K, BATCH = 24, 4, 128
RECORD_ROWS = 70
COLUMN = "features"
# rows of each partition: none a multiple of BATCH or of RECORD_ROWS, so
# every task re-blocks across record batches and pads a masked tail
PARTITIONS = {1: (300,), 2: (300, 212), 3: (300, 212, 145)}


def _chunks(rows, dtype=np.float32, seed=36) -> list:
    """Partitions with a decaying spectrum (separated components) and a
    mean well away from zero (so that centring does work)."""
    rng = np.random.default_rng(seed)
    scale = (1.0 + np.arange(N)) ** -0.5
    return [(rng.normal(size=(r, N)) * scale + 0.5).astype(dtype)
            for r in rows]


def _vector_udt_batches(chunks, batch_rows: int, column: str):
    """``record_batches`` with the rows as all-dense ``VectorUDT`` structs
    (float64 values), what ``mapInArrow`` hands over for a vector column."""
    for chunk in chunks:
        rows = _dense_structs(chunk.astype(np.float64))
        for start in range(0, chunk.shape[0], batch_rows):
            yield pa.RecordBatch.from_arrays(
                [rows.slice(start, batch_rows)], names=[column])


WIRE_FORMS = {"list_float": None, "vector_udt": _vector_udt_batches}


def _stage(monkeypatch, wire="list_float", batch_rows=BATCH, **params):
    """The benchmark's estimator around the front's ``PCA``: the device
    path required ('on': a CPU device here), ragged record batches."""
    if WIRE_FORMS[wire] is not None:
        monkeypatch.setattr(spark_stage, "record_batches", WIRE_FORMS[wire])
    est = spark_stage.SparkStagePCA()
    for name, value in {"k": K, "batchRows": batch_rows,
                        "executorDevice": "on",
                        "recordBatchRows": RECORD_ROWS,
                        "arrowColumn": COLUMN, **params}.items():
        est.set(name, value)
    return est


def _reference(chunks):
    """Plain float64 NumPy, two passes: (components, explained variance
    ratios, mean)."""
    x = np.concatenate(chunks).astype(np.float64)
    mean = x.mean(axis=0)
    xc = x - mean
    evals, evecs = np.linalg.eigh(xc.T @ xc / (x.shape[0] - 1))
    return evecs[:, ::-1][:, :K], evals[::-1][:K] / evals.sum(), mean


def _extra(fit) -> dict:
    return fit.fit_report_.extra


def _span_counts(fit) -> dict:
    counts = {}
    for e in obs_spans.get_recorder().events(fit.fit_report_.trace_id):
        counts[e.name] = counts.get(e.name, 0) + 1
    return counts


@pytest.fixture
def float32_device(monkeypatch):
    """The device's dtype as on a chip: float32 wherever the front asks
    ``models.pca._resolve_dtype`` (the tasks' accumulators, the solve's
    operand, the put), in a suite that otherwise runs x64."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models import pca as pca_module

    monkeypatch.setattr(pca_module, "_resolve_dtype",
                        lambda dtype_param: jnp.float32)


@pytest.fixture
def chip_like(monkeypatch, float32_device):
    """A chip stood in for, as ``tests/test_reblock_staging.py`` does: its
    memory is its own (``put_copies`` yes, a copying ``device_put``, a
    staging pool no other fit has used) and its dtype float32."""
    import jax

    monkeypatch.setattr(streaming, "STAGING", streaming.StagingPool())
    monkeypatch.setattr(streaming, "put_copies", lambda device: True)
    device_put = jax.device_put
    monkeypatch.setattr(
        jax, "device_put", lambda x, *args, **kwargs: device_put(
            x.copy() if isinstance(x, np.ndarray) else x, *args, **kwargs))


@pytest.fixture(params=["float32", "float64"])
def device_dtype(request):
    """Both dtypes a device computes in: the suite's own (x64) and, through
    ``float32_device``, a chip's."""
    if request.param == "float32":
        request.getfixturevalue("float32_device")
    return request.param


# -- the merge of PR 36, kept as the plain reference --------------------------


def _pr36_stats_record_batch(gram, col_sum, count):
    """The float64 form by NumPy, a new array a value, wrapped where it
    lies."""
    def one_list(values):
        flat = np.ascontiguousarray(values, dtype=np.float64).reshape(-1)
        return pa.ListArray.from_arrays(
            pa.array([0, flat.size], type=pa.int32()), pa.array(flat))

    return pa.RecordBatch.from_arrays(
        [one_list(gram), one_list(col_sum),
         pa.array([float(count)], type=pa.float64())],
        schema=aggregate.stats_arrow_schema())


def _pr36_combine_stats(rows):
    """The first row copied, every other added to the copy."""
    gram = col_sum = None
    count = 0
    for row in rows:
        get = row.get if isinstance(row, dict) else row.__getitem__
        g = aggregate._float64_values(get("gram"))
        s = aggregate._float64_values(get("col_sum"))
        n = s.shape[0]
        if gram is None:
            gram, col_sum = np.array(g.reshape(n, n)), np.array(s)
        else:
            gram += g.reshape(n, n)
            col_sum += s
        c = get("count")
        count += float(c.as_py() if hasattr(c, "as_py") else c)
    return gram, col_sum, count


def _pr36_covariance_from_moments(gram, col_sum, count, mean_centering=True,
                                  out=None):
    """Whole arrays, float64, a new one a step; the solve casts."""
    if not mean_centering:
        return gram / max(count - 1, 1), np.zeros_like(col_sum)
    mean = col_sum / max(count, 1)
    block = np.outer(mean, mean)
    block *= count
    return (gram - block) / max(count - 1, 1), mean


# -- the model ----------------------------------------------------------------


@pytest.mark.parametrize("wire", sorted(WIRE_FORMS))
@pytest.mark.parametrize("parts", sorted(PARTITIONS))
def test_front_fit_agrees_with_plain_numpy_float64(monkeypatch, parts, wire):
    chunks = _chunks(PARTITIONS[parts])
    fit = _stage(monkeypatch, wire).fit(iter(chunks))
    pc, evr, mean = _reference(chunks)
    # the tasks accumulate raw moments in float64 (the suite's x64) and the
    # driver centres once, G − N·μμᵀ: with |μ|² ≈ 6 against variances of
    # 0.04–1 the cancellation costs three to four of float64's sixteen
    # digits on the smallest component — 1e-10 leaves two more of room
    np.testing.assert_allclose(fit.mean, mean, rtol=0, atol=1e-12)
    np.testing.assert_allclose(fit.explained_variance, evr, rtol=0,
                               atol=1e-10)
    # components up to sign: |cos| of each against the reference's; the
    # eigenvalue gaps are ≥ 5 % of the top one, so a covariance off by
    # 1e-12 turns a vector by about 1e-11
    np.testing.assert_allclose(np.abs(np.sum(fit.pc * pc, axis=0)), 1.0,
                               rtol=0, atol=1e-9)
    assert fit.pc.shape == (N, K)
    assert _extra(fit)["stage"] == {
        "tasks": parts, "stats_rows": parts,
        "stats_row_bytes": 8 * (N * N + N + 1), "collected_as": "arrow"}


@pytest.mark.parametrize("parts", sorted(PARTITIONS))
def test_front_fit_agrees_with_the_in_process_fit_of_the_same_rows(
        monkeypatch, parts):
    """``models.pca.PCA.fit`` over the same rows as a one-shot iterator runs
    the same accumulate program on one stream where the front runs it on
    ``parts`` streams and adds the sums in float64: rounding apart (the
    order of the additions, the tasks' padded tails), the same model."""
    chunks = _chunks(PARTITIONS[parts])
    fit = _stage(monkeypatch).fit(iter(chunks))
    local = LocalPCA().setK(K).set("batchRows", BATCH).fit(iter(chunks))
    np.testing.assert_allclose(fit.mean, local.mean, rtol=0, atol=1e-13)
    np.testing.assert_allclose(fit.explained_variance,
                               local.explained_variance, rtol=0, atol=1e-11)
    np.testing.assert_allclose(fit.pc, local.pc, rtol=0, atol=1e-9)
    assert fit.svd_solver_used_ == local.svd_solver_used_ == "eigh"
    # program for program: both took the XLA one-pass step (CPU), once a
    # device batch
    assert _extra(fit)["ingest"]["accumulate_calls"]["xla"] == sum(
        -(-rows // BATCH) for rows in PARTITIONS[parts])
    assert local.fit_report_.extra["ingest"]["accumulate_calls"][
        "xla"] == -(-sum(PARTITIONS[parts]) // BATCH)


@pytest.mark.parametrize("wire", sorted(WIRE_FORMS))
@pytest.mark.parametrize("parts", sorted(PARTITIONS))
def test_front_fit_returns_the_bits_the_merge_of_pr36_returned(
        monkeypatch, parts, wire, device_dtype):
    """The seams and the arithmetic did not move: the same rows through the
    row, the sum, the centring and the cast as PR 36 had them (NumPy's
    float64 form of the fetched moments; the first row copied and the rest
    added; the sum centred where it lies, in float64; ``np.asarray(cov,
    float32)`` inside the solve where the device computes in float32) give
    ``pc``, ``explainedVariance`` and ``mean`` equal to the last bit."""
    chunks = _chunks(PARTITIONS[parts])
    now = _stage(monkeypatch, wire).fit(iter(chunks))
    with monkeypatch.context() as before:
        before.setattr(device_aggregate, "stats_record_batch",
                       _pr36_stats_record_batch)
        before.setattr(front, "combine_stats", _pr36_combine_stats)
        before.setattr(front, "covariance_from_moments",
                       _pr36_covariance_from_moments)
        then = _stage(monkeypatch, wire).fit(iter(chunks))
    assert then.pc.dtype == now.pc.dtype == np.float64
    for field in ("pc", "explained_variance", "mean"):
        assert np.array_equal(getattr(now, field), getattr(then, field)), \
            field
    # and the two did not run the same code
    assert (_extra(now)["host_arrays"]["pooled"]
            > _extra(then).get("host_arrays", {"pooled": 0})["pooled"])


def test_an_empty_partition_adds_nothing(monkeypatch):
    chunks = _chunks(PARTITIONS[2])
    empty = np.zeros((0, N), dtype=np.float32)
    two = _stage(monkeypatch).fit(iter(chunks))
    three = _stage(monkeypatch).fit(iter([chunks[0], empty, chunks[1]]))
    assert np.array_equal(three.pc, two.pc)
    assert np.array_equal(three.mean, two.mean)
    # its task ran and handed nothing back
    assert _extra(three)["stage"]["tasks"] == 3
    assert _extra(three)["stage"]["stats_rows"] == 2
    assert _span_counts(three)[device_aggregate.SPAN_TASK] == 3
    assert _span_counts(three)[device_aggregate.SPAN_HANDBACK] == 2


def test_a_task_that_raises_fails_the_fit(monkeypatch):
    """A null row in the second partition's second record batch: the reader
    refuses it (``data/arrow.py``), the task raises, the fit raises —
    nothing is dropped and no model comes back."""
    real = spark_stage.record_batches

    def with_a_null(chunks, batch_rows, column):
        for i, batch in enumerate(real(chunks, batch_rows, column)):
            if i == 1 and chunks[0].shape[0] == PARTITIONS[2][1]:
                rows = batch.column(0).to_pylist()
                rows[3] = None
                batch = pa.RecordBatch.from_arrays(
                    [pa.array(rows, type=batch.schema.field(0).type)],
                    names=[column])
            yield batch

    monkeypatch.setattr(spark_stage, "record_batches", with_a_null)
    with pytest.raises(ValueError, match="null"):
        _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[2])))


# -- the statistics row -------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_the_device_paths_stats_row_is_a_buffer_not_a_python_list(dtype):
    """The task hands the row maker the moments as fetched — the device's
    dtype — and the row's values are their exact float64 image: Arrow's
    cast of a view of the fetched buffer, into memory that is the row's
    own; float64 moments are wrapped where they lie."""
    handed = {}

    def recording(gram, col_sum, count):
        handed.update(gram=gram, col_sum=col_sum)
        return aggregate.stats_record_batch(gram, col_sum, count)

    (chunk,) = _chunks(PARTITIONS[1])
    (row,) = device_aggregate.partition_gram_stats_device(
        spark_stage.record_batches([chunk], RECORD_ROWS, COLUMN), COLUMN,
        dtype=dtype, batch_rows=BATCH, row=recording)
    assert row.schema == aggregate.stats_arrow_schema()
    assert row.num_rows == 1
    gram = row.column("gram").values.to_numpy(zero_copy_only=True)
    col_sum = row.column("col_sum").values.to_numpy(zero_copy_only=True)
    assert isinstance(handed["gram"], np.ndarray)
    assert handed["gram"].dtype == handed["col_sum"].dtype == dtype
    assert handed["gram"].shape == (N, N)
    # bit for bit what NumPy's float64 form of the fetched moments reads
    assert gram.dtype == col_sum.dtype == np.float64
    assert np.array_equal(gram, handed["gram"].astype(np.float64).ravel())
    assert np.array_equal(col_sum, handed["col_sum"].astype(np.float64))
    assert row.equals(_pr36_stats_record_batch(
        handed["gram"], handed["col_sum"], chunk.shape[0]))
    # a cast writes the row's own buffer; nothing to cast, nothing copied
    assert np.shares_memory(gram, handed["gram"]) == (
        dtype == "float64")
    x = chunk.astype(np.float64)
    rtol = 1e-12 if dtype == "float64" else 1e-5
    np.testing.assert_allclose(gram.reshape(N, N), x.T @ x, rtol=rtol)
    np.testing.assert_allclose(col_sum, x.sum(axis=0), rtol=rtol)
    assert row.column("count").to_pylist() == [float(chunk.shape[0])]
    # the schema and the bytes on the wire are what they were
    assert aggregate.stats_spark_ddl() == (
        "gram array<double>, col_sum array<double>, count double")
    assert gram.nbytes + col_sum.nbytes + 8 == 8 * (N * N + N + 1)
    # and it survives Spark's hand-over as a view of the received buffer
    back = spark_stage.ipc_round_trip(row)
    (arrow_row,) = aggregate.arrow_stats_rows(back)
    received = aggregate._float64_values(arrow_row["gram"])
    assert np.array_equal(received, gram)
    assert not received.flags.owndata and not received.flags.writeable


def test_the_row_dict_callers_keep_float64_numpy(float32_device):
    """``partition_gram_stats_device``'s documented row — a dict with the
    moments as float64 NumPy arrays — whatever the device computed in."""
    (chunk,) = _chunks(PARTITIONS[1])
    (row,) = device_aggregate.partition_gram_stats_device(
        [chunk], None, batch_rows=BATCH)
    assert isinstance(row, dict) and set(row) == {"gram", "col_sum", "count"}
    assert isinstance(row["gram"], np.ndarray)
    assert row["gram"].dtype == row["col_sum"].dtype == np.float64
    assert row["gram"].shape == (N, N) and row["count"] == chunk.shape[0]
    x = chunk.astype(np.float64)
    np.testing.assert_allclose(row["gram"], x.T @ x, rtol=1e-5)
    (xy,) = device_aggregate.partition_xy_stats_device(
        [(chunk, chunk[:, 0])], "f", "y")
    assert xy["gram"].dtype == np.float64
    assert xy["gram"].shape == (N + 1, N + 1)


def test_rows_of_tasks_held_side_by_side_stay_intact(float32_device):
    """A frame may hand every yielded row on untouched (``_collect_stats``
    holds them all until the merge): each row's float64 buffer is its own,
    so a third task's hand-back leaves the first two as they were."""
    chunks = _chunks(PARTITIONS[3])

    def task(chunk):
        (row,) = device_aggregate.partition_gram_stats_device_arrow(
            spark_stage.record_batches([chunk], RECORD_ROWS, COLUMN),
            COLUMN, batch_rows=BATCH)
        return row

    def values(row):
        return row.column("gram").values.to_numpy(zero_copy_only=True)

    first, second = task(chunks[0]), task(chunks[1])
    kept = values(first).copy(), values(second).copy()
    third = task(chunks[2])
    assert np.array_equal(values(first), kept[0])
    assert np.array_equal(values(second), kept[1])
    rows = (first, second, third)
    assert not any(np.shares_memory(values(a), values(b))
                   for i, a in enumerate(rows) for b in rows[i + 1:])
    for row, chunk in zip(rows, chunks):
        x = chunk.astype(np.float64)
        np.testing.assert_allclose(values(row).reshape(N, N), x.T @ x,
                                   rtol=1e-5)


ROW_FORMS = {
    "arrow": lambda g, s, c: next(aggregate.arrow_stats_rows(
        aggregate.stats_record_batch(g, s, c))),
    "numpy": lambda g, s, c: {"gram": g, "col_sum": s, "count": c},
    "list": lambda g, s, c: {"gram": g.ravel().tolist(),
                             "col_sum": s.tolist(), "count": float(c)},
}


@pytest.mark.parametrize("n_rows", [1, 2, 3])
@pytest.mark.parametrize("form", sorted(ROW_FORMS))
def test_combine_stats_takes_arrow_numpy_and_list_rows_alike(form, n_rows):
    rng = np.random.default_rng(4)
    parts = [(rng.normal(size=(N, N)), rng.normal(size=N), 10 + i)
             for i in range(n_rows)]
    gram, col_sum, count = aggregate.combine_stats(
        ROW_FORMS[form](*part) for part in parts)
    # the plain left-to-right float64 sum, bit for bit: the first two rows
    # added in one pass are what a copy of the first plus the second reads
    plain_g, plain_s = parts[0][0].copy(), parts[0][1].copy()
    for g, s, _ in parts[1:]:
        plain_g += g
        plain_s += s
    assert np.array_equal(gram, plain_g)
    assert np.array_equal(col_sum, plain_s)
    assert count == float(sum(10 + i for i in range(n_rows)))
    assert gram.shape == (N, N) and col_sum.shape == (N,)
    assert gram.dtype == col_sum.dtype == np.float64
    assert gram.flags.writeable and col_sum.flags.writeable
    assert gram.flags.c_contiguous
    # the sums are the combiner's own arrays, no row's
    assert not any(np.shares_memory(gram, part[0]) for part in parts)
    assert not any(np.shares_memory(col_sum, part[1]) for part in parts)
    gram += 1.0  # and stay so: nothing else reads this memory
    assert np.array_equal(gram, plain_g + 1.0)


def test_combine_stats_of_no_row_raises():
    with pytest.raises(ValueError, match="empty dataset"):
        aggregate.combine_stats(iter(()))


def test_the_sum_comes_out_of_arrows_pool_and_goes_back():
    """``pooled_matrix`` is an array over a buffer of Arrow's memory pool,
    owned by the array: the pool counts its bytes while any array over it
    lives and has them back when the last is dropped."""
    before = pa.total_allocated_bytes()
    a = aggregate.pooled_matrix(N, np.float32)
    assert a.shape == (N, N) and a.dtype == np.float32
    assert a.flags.writeable and a.flags.c_contiguous
    assert not a.flags.owndata
    assert pa.total_allocated_bytes() - before >= a.nbytes
    view = a[1:]
    del a
    assert pa.total_allocated_bytes() - before >= view.base.nbytes
    del view
    assert pa.total_allocated_bytes() == before
    assert aggregate.pooled_matrix(3).dtype == np.float64


def test_the_merge_centres_once_in_float64():
    """(ΣG − N·μμᵀ)/(N − 1) of the summed moments is the covariance of all
    rows; block by block or whole, the same numbers."""
    x = np.concatenate(_chunks(PARTITIONS[3])).astype(np.float64)
    cov, mean = aggregate.covariance_from_moments(
        x.T @ x, x.sum(axis=0), x.shape[0])
    np.testing.assert_allclose(mean, x.mean(axis=0), rtol=0, atol=1e-14)
    np.testing.assert_allclose(cov, np.cov(x, rowvar=False), rtol=0,
                               atol=1e-12)
    whole = (x.T @ x - x.shape[0] * np.outer(mean, mean)) / (x.shape[0] - 1)
    assert np.array_equal(cov, whole)
    # where the caller owns the sum, the covariance is written over it
    own = x.T @ x
    in_place, _ = aggregate.covariance_from_moments(
        own, x.sum(axis=0), x.shape[0], out=own)
    assert in_place is own and np.array_equal(own, cov)
    raw, zero = aggregate.covariance_from_moments(
        x.T @ x, x.sum(axis=0), x.shape[0], mean_centering=False)
    assert np.array_equal(raw, x.T @ x / (x.shape[0] - 1))
    assert not zero.any()
    with pytest.raises(ValueError, match="more than one row"):
        aggregate.covariance_from_moments(x[:1].T @ x[:1], x[0], 1)


@pytest.mark.parametrize("centering", [True, False])
@pytest.mark.parametrize("n", [N, 1500])  # one block; 699 rows a block
def test_the_centring_rounds_once_into_the_solves_operand(centering, n):
    """``out=`` of another dtype takes every element computed in float64
    and rounded once at the store: what ``.astype(float32)`` of the float64
    covariance reads, bit for bit, block by block or whole."""
    rng = np.random.default_rng(n)
    x = rng.normal(size=(3 * n // 2, n)) * 3.0 + 1.0
    gram, col_sum, count = x.T @ x, x.sum(axis=0), float(x.shape[0])
    cov64, mean64 = aggregate.covariance_from_moments(
        gram, col_sum, count, centering)
    operand = aggregate.pooled_matrix(n, np.float32)
    cov32, mean = aggregate.covariance_from_moments(
        gram, col_sum, count, centering, out=operand)
    assert cov32 is operand and cov32.dtype == np.float32
    assert np.array_equal(cov32, cov64.astype(np.float32))
    assert np.array_equal(mean, mean64) and mean.dtype == np.float64
    whole = (gram - count * np.outer(mean64, mean64)) / (count - 1) \
        if centering else gram / (count - 1)
    assert np.array_equal(cov64, whole)
    assert np.array_equal(cov32, whole.astype(np.float32))
    # not what a subtraction or a division carried out in float32 reads
    assert not np.array_equal(
        cov32, (gram.astype(np.float32) / np.float32(count - 1))
        if not centering else
        ((gram - count * np.outer(mean64, mean64)).astype(np.float32)
         / np.float32(count - 1)))


# -- the one loop, the one solve ----------------------------------------------


def test_the_front_reports_the_one_loops_counters(monkeypatch):
    # a pool no earlier fit of this process has left a buffer in
    monkeypatch.setattr(streaming, "STAGING", streaming.StagingPool())
    rows = PARTITIONS[3]
    fit = _stage(monkeypatch).fit(iter(_chunks(rows)))
    ingest = _extra(fit)["ingest"]
    batches = [-(-r // BATCH) for r in rows]  # 3, 2, 2: each task pads one
    assert ingest["passes"] == 3 and ingest["batches"] == sum(batches)
    # what crossed is whole device batches of ``batchRows`` rows — not the
    # old loop's record batches padded to power-of-two buckets (a 70-row
    # batch in 256: 10 record batches, 2560 rows put)
    assert ingest["rows_put"] == sum(batches) * BATCH
    assert ingest["bytes_put"] == sum(batches) * BATCH * N * 8
    assert ingest["chunks"] == sum(-(-r // RECORD_ROWS) for r in rows)
    assert ingest["chunk_rows_max"] == RECORD_ROWS
    assert ingest["chunk_rows_min"] == min(r % RECORD_ROWS for r in rows)
    assert ingest["chunks_viewed"] == ingest["chunks"]
    assert ingest["batches_copied"] == sum(batches)
    assert ingest["batches_viewed"] == 0
    assert (ingest["staging_reused"] + ingest["staging_fresh"]
            == ingest["batches_copied"])
    assert ingest["accumulate_calls"] == {"mean": 0, "pallas": 0,
                                          "xla": sum(batches)}
    assert ingest["puts_in_flight_max"] == streaming.PUTS_IN_FLIGHT
    assert ingest["put_waits"] == sum(b - 2 for b in batches if b > 2)
    assert ingest["per_chip"][0]["rows_put"] == ingest["rows_put"]
    assert fit.fit_report_.rows == sum(rows)
    # nothing of a task is left behind on the report
    assert device_aggregate._TASK_REPORTS not in _extra(fit)


@pytest.mark.parametrize("batch_rows, batches", [(64, 5 + 4), (256, 2 + 1)])
def test_batch_rows_reaches_the_executor(monkeypatch, batch_rows, batches):
    fit = _stage(monkeypatch, batch_rows=batch_rows).fit(
        iter(_chunks(PARTITIONS[2])))
    ingest = _extra(fit)["ingest"]
    assert ingest["batches"] == batches
    assert ingest["rows_put"] == batches * batch_rows


def test_the_front_has_the_params_and_refuses_others():
    est = front.PCA(k=3, inputCol="f", batchRows=512,
                    gramPrecision="bfloat16")
    assert (est.getBatchRows(), est.getGramPrecision()) == (512, "bfloat16")
    assert front.PCA().getBatchRows() == 0
    assert front.PCA().getGramPrecision() == "auto"
    assert front.PCA().getExecutorDevice() == "auto"
    assert front.PCA()._gram_precision() is None
    assert est._gram_precision() is not None
    with pytest.raises(ValueError, match="gramPrecision"):
        front.PCA(gramPrecision="float8")._gram_precision()
    with pytest.raises(Exception):  # what a parent's front says to batchRows
        spark_stage.SparkStagePCA().set("noSuchParam", 1)


def test_the_drivers_solve_is_the_gated_one(monkeypatch):
    fit = _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[2])))
    solve = _extra(fit)["solve"]
    # n = 24 resolves to the dense program, which runs ungated; the note is
    # what ``pca_from_covariance_gated`` leaves and nothing else does
    assert solve == {"solver": "eigh", "gate": "ungated",
                     "residual_ratio": None, "programs": 1}
    assert fit.svd_solver_used_ == "eigh"


def test_a_wide_fit_resolves_to_the_gated_randomized_program(monkeypatch):
    """Where ``auto`` picks the randomized solve (k ≪ n on a large
    covariance) the front runs it through the gate, as ``PCA.fit`` does;
    the dense ``eigh`` the front's old solve always ran is the fallback
    only."""
    from spark_rapids_ml_tpu.ops.eigh import resolve_auto_solver

    n, k = 1024, 16
    assert resolve_auto_solver(n, k) == "randomized"
    rng = np.random.default_rng(7)
    scale = (1.0 + np.arange(n)) ** -0.5
    chunks = [(rng.normal(size=(1500, n)) * scale).astype(np.float32)
              for _ in range(2)]
    est = spark_stage.SparkStagePCA()
    for name, value in {"k": k, "batchRows": 1024, "executorDevice": "on",
                        "recordBatchRows": 700, "arrowColumn": COLUMN
                        }.items():
        est.set(name, value)
    fit = est.fit(iter(chunks))
    solve = _extra(fit)["solve"]
    assert solve["solver"] == "randomized" and solve["gate"] == "passed"
    assert solve["programs"] == 1 and solve["residual_ratio"] < 0.05
    assert fit.svd_solver_used_ == "randomized"


# -- the solve's operand, and the count of Gram-sized host arrays -------------


def _operands(monkeypatch) -> list:
    """What the front hands the solve, fit by fit."""
    seen, real = [], front.solve_covariance

    def recording(cov, *args, **kwargs):
        seen.append(cov)
        return real(cov, *args, **kwargs)

    monkeypatch.setattr(front, "solve_covariance", recording)
    return seen


def test_the_operand_is_of_the_dtype_the_solve_puts(monkeypatch,
                                                    float32_device):
    """A float32 device solve is handed a float32 covariance, written by
    the centring into memory out of Arrow's pool: ``solve_on_chip``'s
    ``np.asarray`` finds nothing to cast."""
    seen = _operands(monkeypatch)
    chunks = _chunks(PARTITIONS[2])
    fit = _stage(monkeypatch).fit(iter(chunks))
    (operand,) = seen
    assert operand.dtype == np.float32 and operand.shape == (N, N)
    assert not operand.flags.owndata  # over a pooled buffer, not NumPy's
    assert np.asarray(operand, dtype=np.float32) is operand
    pc, evr, mean = _reference(chunks)
    np.testing.assert_allclose(fit.mean, mean, rtol=0, atol=1e-6)
    np.testing.assert_allclose(fit.explained_variance, evr, rtol=0,
                               atol=1e-5)
    np.testing.assert_allclose(np.abs(np.sum(fit.pc * pc, axis=0)), 1.0,
                               rtol=0, atol=1e-4)


def test_a_float64_solve_is_handed_the_sum_centred_where_it_lies(
        monkeypatch, device_dtype):
    """``useXlaSvd=False`` (the host's LAPACK) solves a float64 covariance
    whatever the device computes in, and so does a device in x64: the
    operand is the sum itself, no second array."""
    seen = _operands(monkeypatch)
    sums, real = [], front.combine_stats

    def recording(rows):
        sums.append(real(rows))
        return sums[-1]

    monkeypatch.setattr(front, "combine_stats", recording)
    chunks = _chunks(PARTITIONS[2])
    host = _stage(monkeypatch, useXlaSvd=False).fit(iter(chunks))
    assert seen[0].dtype == np.float64 and seen[0] is sums[0][0]
    assert host.svd_solver_used_ is None
    assert "solve" not in _extra(host)  # the gated solve's note
    pc, evr, mean = _reference(chunks)
    tol = 1e-10 if device_dtype == "float64" else 1e-5
    np.testing.assert_allclose(host.explained_variance, evr, rtol=0,
                               atol=tol)
    if device_dtype == "float64":  # x64: the device solve's operand too
        _stage(monkeypatch).fit(iter(chunks))
        assert seen[1].dtype == np.float64 and seen[1] is sums[1][0]


def test_on_the_cpu_backend_an_operand_is_never_reused():
    """``put_copies`` says no for the CPU backend: an aligned host array —
    and one over Arrow's pool is — *becomes* the device array. The operand
    is owned by whatever reads it, so while that device array lives the
    pool has not got the memory back and no later fit can be handed it;
    a chip copies, and the memory goes back when the fit drops it."""
    import jax

    device = jax.local_devices()[0]
    assert device.platform == "cpu" and not streaming.put_copies(device)
    before = pa.total_allocated_bytes()
    operand = aggregate.pooled_matrix(N, np.float32)
    operand[:] = 1.0
    on_device = jax.block_until_ready(jax.device_put(operand, device))
    operand[0, 0] = 2.0
    assert float(np.asarray(on_device)[0, 0]) == 2.0  # one memory
    operand[0, 0] = 1.0
    del operand
    assert pa.total_allocated_bytes() > before  # the device array's now
    later = [aggregate.pooled_matrix(N, np.float32) for _ in range(8)]
    for other in later:  # what later fits are handed is other memory
        other[:] = 3.0
    assert np.array_equal(np.asarray(on_device),
                          np.ones((N, N), np.float32))
    del later, other, on_device
    gc.collect()  # the device array and its host view refer to each other
    assert pa.total_allocated_bytes() == before


# name → (fixture, collected as Arrow, the counts for ``parts`` rows)
HOST_ARRAY_PATHS = {
    # x64 on the CPU: the fetch is a view of the device's memory, the row
    # wraps it, the float64 operand is the sum — one array made, the sum
    "cpu_x64": (None, True, lambda parts: {"numpy": 0, "pooled": 1}),
    # float32 moments: a cast a row, the sum, the float32 operand
    "float32": ("float32_device", True,
                lambda parts: {"numpy": 0, "pooled": parts + 2}),
    # a chip: a fetch a task besides — 2 / 4 with two partitions
    "chip": ("chip_like", True,
             lambda parts: {"numpy": parts, "pooled": parts + 2}),
    # collect() of Rows: each Gram a Python list read into a new array
    "chip_rows": ("chip_like", False,
                  lambda parts: {"numpy": 2 * parts, "pooled": parts + 2}),
}


@pytest.mark.parametrize("parts", sorted(PARTITIONS))
@pytest.mark.parametrize("path", sorted(HOST_ARRAY_PATHS))
def test_host_arrays_reads_what_the_path_made(monkeypatch, request, path,
                                              parts):
    fixture, as_arrow, expected = HOST_ARRAY_PATHS[path]
    if fixture:
        request.getfixturevalue(fixture)
    if not as_arrow:
        monkeypatch.delattr(spark_stage._MappedStage, "toArrow")
    fit = _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[parts])))
    # a single row's sum is a copy of it, and counts like any other sum
    assert _extra(fit)["host_arrays"] == expected(parts)
    # beside the stage's note, which the benchmark's test compares whole
    assert "host_arrays" not in _extra(fit)["stage"]


def test_a_task_outside_a_fit_counts_nothing(float32_device):
    """A task in another process than its driver runs under no report:
    its arrays are made all the same and nothing is kept of the count."""
    from spark_rapids_ml_tpu.obs import report

    (chunk,) = _chunks(PARTITIONS[1])
    (row,) = device_aggregate.partition_gram_stats_device_arrow(
        spark_stage.record_batches([chunk], RECORD_ROWS, COLUMN), COLUMN,
        batch_rows=BATCH)
    assert row.num_rows == 1
    assert aggregate.HOST_ARRAYS not in report.current_fit().extra


# -- spans and keys -----------------------------------------------------------


@pytest.mark.parametrize("parts", sorted(PARTITIONS))
def test_the_stage_leaves_its_spans_and_keys(monkeypatch, parts):
    from spark_rapids_ml_tpu.models import pca as pca_module

    fit = _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[parts])))
    spans = _span_counts(fit)
    assert spans[device_aggregate.SPAN_TASK] == parts
    assert spans[device_aggregate.SPAN_HANDBACK] == parts
    assert spans[front.SPAN_MERGE] == 1
    # inside each task the one loop's spans, then the driver's as PCA.fit's
    assert spans[pca_module.SPAN_STREAMED_COV] == parts
    assert spans[streaming.SPAN_PASS_STATS] == parts
    assert spans[streaming.SPAN_SYNC_COV] == parts
    assert spans[pca_module.SPAN_FIT] == 1
    assert spans[pca_module.SPAN_XLA_EIGH] == 1
    assert spans[pca_module.SPAN_FETCH] == 1
    timings = fit.fit_timings_
    for key in (device_aggregate.PHASE_TASK, device_aggregate.PHASE_HANDBACK,
                front.PHASE_MERGE, "covariance", streaming.PHASE_PUT,
                streaming.PHASE_SYNC, streaming.PHASE_NEXT,
                *streaming.PHASE_NEXT_PART.values(),
                streaming.PHASE_DISPATCH, "solve", "fetch",
                spark_stage.COLLECT_PHASE):
        assert timings[key] >= 0.0, key
    # a task holds its stream and its hand-back
    assert timings[device_aggregate.PHASE_TASK] >= (
        timings["covariance"] + timings[device_aggregate.PHASE_HANDBACK])
    # the front's own model carries them too, and the report
    assert fit.model.fit_timings_[front.PHASE_MERGE] == timings[
        front.PHASE_MERGE]
    assert fit.fit_report_.phases[front.PHASE_MERGE] == pytest.approx(
        timings[front.PHASE_MERGE])


@pytest.mark.parametrize("parts", sorted(PARTITIONS))
def test_the_action_span_holds_the_tasks(monkeypatch, parts):
    """``stage:action`` wraps the call that runs the tasks lazily and
    collects their rows: every ``stage:task`` lies inside it, the merge
    after it, and its self time is what the tasks leave of it."""
    fit = _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[parts])))
    events = obs_spans.get_recorder().events(fit.fit_report_.trace_id)
    (action,) = [e for e in events if e.name == front.SPAN_ACTION]
    tasks = [e for e in events if e.name == device_aggregate.SPAN_TASK]
    (merge,) = [e for e in events if e.name == front.SPAN_MERGE]
    assert len(tasks) == parts
    for task in tasks:
        assert action.ts_us <= task.ts_us
        assert task.ts_us + task.dur_us <= action.ts_us + action.dur_us
    assert merge.ts_us >= action.ts_us + action.dur_us
    timings = fit.fit_timings_
    assert timings[front.PHASE_ACTION] >= timings[device_aggregate.PHASE_TASK]
    # the stand-in's IPC round trips happen inside the action, beside the
    # tasks: Spark's share, seen from the program's own span
    assert timings[front.PHASE_ACTION] - timings[
        device_aggregate.PHASE_TASK] >= 0.9 * timings[
            spark_stage.COLLECT_PHASE]
    assert fit.fit_report_.phases[front.PHASE_ACTION] == pytest.approx(
        timings[front.PHASE_ACTION])
    # tasks in other processes: the action's span and key are still there
    assert front.SPAN_ACTION not in streaming.STREAM_SPANS


def test_the_stages_names_are_the_benchmarks():
    path = os.path.join(ROOT, "benchmarks", "work", "stage.py")
    spec = importlib.util.spec_from_file_location("stage_names", path)
    stage = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stage)
    assert stage.SPANS == {"task": device_aggregate.SPAN_TASK,
                           "handback": device_aggregate.SPAN_HANDBACK,
                           "merge": front.SPAN_MERGE}
    assert stage.PHASES == {"task": device_aggregate.PHASE_TASK,
                            "handback": device_aggregate.PHASE_HANDBACK,
                            "merge": front.PHASE_MERGE}
    assert stage.COLLECT_PHASE == spark_stage.COLLECT_PHASE
    # not the stream's and not the listed program spans: the accepted idle
    # readers give their idle seconds to the enclosing coarse span
    assert not set(stage.SPANS.values()) & set(streaming.STREAM_SPANS)


def test_tasks_in_another_process_leave_the_driver_no_report(monkeypatch):
    """What a real cluster gives: the rows arrive, the tasks' spans and
    counters do not. The stage says so, and the loop's keys are absent."""
    monkeypatch.setattr(device_aggregate, "_report_task", lambda *a: None)
    fit = _stage(monkeypatch).fit(iter(_chunks(PARTITIONS[2])))
    assert _extra(fit)["stage"]["tasks"] == 0
    assert _extra(fit)["stage"]["stats_rows"] == 2
    assert front.PHASE_MERGE in fit.fit_timings_
    assert "solve" in fit.fit_timings_ and "fetch" in fit.fit_timings_
    assert device_aggregate.PHASE_TASK not in fit.fit_timings_
    assert "covariance" not in fit.fit_timings_


def test_rows_collected_without_arrow_fit_alike(monkeypatch):
    """A frame with no ``toArrow()`` (pyspark < 4.0): ``collect()`` of rows
    of Python values — slow at width, and the same model."""
    chunks = _chunks(PARTITIONS[2])
    arrow = _stage(monkeypatch).fit(iter(chunks))
    monkeypatch.delattr(spark_stage._MappedStage, "toArrow")
    rows = _stage(monkeypatch).fit(iter(chunks))
    assert _extra(rows)["stage"]["collected_as"] == "rows"
    assert np.array_equal(rows.pc, arrow.pc)
    assert np.array_equal(rows.explained_variance, arrow.explained_variance)


# -- the loop's one-pass door -------------------------------------------------


@pytest.mark.parametrize("centering", [True, False])
def test_stream_covariance_one_pass_is_stream_gram_stats(centering):
    """``stream_covariance``'s one-pass branch is ``stream_gram_stats`` and
    the centring: the same raw moments, bit for bit, and the covariance
    ``covariance_from_stats`` makes of them."""
    from spark_rapids_ml_tpu.data.batches import BatchSource
    from spark_rapids_ml_tpu.ops.covariance import covariance_from_stats

    chunks = _chunks(PARTITIONS[3])
    cov, mean, count = streaming.stream_covariance(
        BatchSource(iter(chunks), batch_rows=BATCH),
        mean_centering=centering)
    ingest = streaming.IngestTrace()
    stats = streaming.stream_gram_stats(
        BatchSource(iter(chunks), batch_rows=BATCH), ingest=ingest)
    assert isinstance(stats, streaming.GramStats)
    assert int(stats.count) == int(count) == sum(PARTITIONS[3])
    assert np.array_equal(cov, covariance_from_stats(
        stats.gram, stats.col_sum, stats.count, mean_centering=centering))
    x = np.concatenate(chunks).astype(np.float64)
    np.testing.assert_allclose(stats.gram, x.T @ x, rtol=1e-5)
    np.testing.assert_allclose(stats.col_sum, x.sum(axis=0), rtol=1e-5)
    assert ingest.counters["passes"] == 1
    assert ingest.counters["accumulate_calls"]["xla"] == 6
