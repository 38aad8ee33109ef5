"""Binary LogisticRegression on the streamed loop (CPU): the labelled rows
cross once through ``ops.streaming.IngestTrace``, each Newton step walks
the batches kept on the chip and puts the rest again, and solves its
system on the device.

The CPU reports no ``memory_stats()``, so nothing is kept here unless a
test stands in for the chip through the seam ``streaming.keep_budget_bytes``
(as ``tests/test_kmeans_streamed.py`` does for Lloyd): all, some or none of
the batches kept, the fit has to be plain Newton from w = 0 — a NumPy
float64 Newton over the same rows, step for step. An array, a frame (with
row weights or without) and a callable all walk that loop. The
benchmark's names (``benchmarks/work/newton.py``) are held against what
the program emits.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import LogisticRegression, obs
from spark_rapids_ml_tpu.data.batches import ColumnBlocks, auto_batch_rows
from spark_rapids_ml_tpu.data.frame import as_vector_frame
from spark_rapids_ml_tpu.models import linear_regression
from spark_rapids_ml_tpu.models import logistic_regression as lr_module
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import logreg_kernel, streaming

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, BATCH, LAM = 6, 128, 1e-3
CHUNK = 173  # uneven against BATCH: joined batches and a masked tail
ROWS = 600  # four whole batches and a tail of 88 rows
BATCHES = -(-ROWS // BATCH)
WIDTH = N + 1  # [X | y]
EVERYTHING = 1 << 40


def _rows(seed: int = 3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, N))
    beta = rng.normal(size=N)
    y = (x @ beta + 0.4 + 1.5 * rng.normal(size=ROWS) > 0).astype(np.float64)
    return x, y


def _factory(x, y):
    return lambda: ((x[i:i + CHUNK], y[i:i + CHUNK])
                    for i in range(0, len(y), CHUNK))


def _estimator(**params):
    est = (LogisticRegression().setRegParam(LAM).setMaxIter(6)
           .setTol(0.0).set("batchRows", BATCH))
    for name, value in params.items():
        est.set(name, value)
    return est


def _fit(x, y, budget=None, monkeypatch=None, **params):
    if budget is not None:
        monkeypatch.setattr(streaming, "keep_budget_bytes",
                            lambda *a, **k: budget)
    return _estimator(**params).fit(_factory(x, y))


def _numpy_newton(x, y, lam, steps, fit_intercept=True, weights=None):
    """``steps`` float64 Newton steps from w = 0 on Spark's objective: the
    weighted MLE where rows have ``weights`` (each row's loss times its
    weight, over the weights' sum)."""
    n = x.shape[1]
    v = np.ones(len(y)) if weights is None else weights
    w = np.zeros(n + 1)
    for _ in range(steps):
        p = 1.0 / (1.0 + np.exp(-(x @ w[:n] + w[n])))
        r, s = v * (p - y), v * p * (1.0 - p)
        g = np.append(x.T @ r / v.sum() + lam * w[:n], r.sum() / v.sum())
        h = np.zeros((n + 1, n + 1))
        h[:n, :n] = x.T @ (x * s[:, None]) / v.sum() + lam * np.eye(n)
        h[:n, n] = h[n, :n] = x.T @ s / v.sum()
        h[n, n] = s.sum() / v.sum()
        if not fit_intercept:
            g[n], h[:n, n], h[n, :n], h[n, n] = 0.0, 0.0, 0.0, 1.0
        w = w - np.linalg.solve(h, g)
    return w[:n], w[n]


def _assert_plain_newton(model, x, y, atol=1e-9, **kwargs):
    coef, b = _numpy_newton(x, y, LAM, model.n_iter_, **kwargs)
    np.testing.assert_allclose(model.coefficients, coef, rtol=0, atol=atol)
    assert model.intercept == pytest.approx(b, abs=atol)


# -- the loop at every keep level ---------------------------------------------


@pytest.mark.parametrize("kept", [BATCHES, 2, 0], ids=["all", "some", "none"])
def test_the_loop_is_plain_newton_at_every_keep_level(monkeypatch, kept):
    x, y = _rows()
    batch_nbytes = BATCH * WIDTH * 8  # the suite runs float64 (x64)
    model = _fit(x, y, kept * batch_nbytes, monkeypatch)
    _assert_plain_newton(model, x, y)
    ingest = model.fit_report_.extra["ingest"]
    newton = model.fit_report_.extra["newton"]
    steps = newton["steps"]
    assert steps == model.n_iter_ == 6
    assert ingest["batches_kept"] == kept
    assert ingest["passes"] == steps  # the first walk is step 1's
    # every batch crosses once; a batch not kept crosses again every step
    assert ingest["batches"] == kept + (BATCHES - kept) * steps
    assert newton["batches_from_kept"] == kept * (steps - 1)
    assert newton["batches_put_again"] == (BATCHES - kept) * (steps - 1)
    assert model.fit_report_.rows == ROWS


def test_keeping_changes_nothing_in_the_answer(monkeypatch):
    x, y = _rows(seed=4)
    ample = _fit(x, y, EVERYTHING, monkeypatch)
    one = _fit(x, y, BATCH * WIDTH * 8, monkeypatch)
    assert ample.fit_report_.extra["newton"]["batches_put_again"] == 0
    assert one.fit_report_.extra["ingest"]["batches_kept"] == 1
    assert one.fit_report_.extra["newton"]["batches_put_again"] > 0
    np.testing.assert_array_equal(one.coefficients, ample.coefficients)
    assert one.intercept == ample.intercept


def test_the_budget_is_the_steps_own_reserve(monkeypatch):
    asked = []

    def rule(device, reserve_nbytes):
        asked.append(reserve_nbytes)
        return 0

    monkeypatch.setattr(streaming, "keep_budget_bytes", rule)
    _fit(*_rows(), maxIter=2)
    assert asked == [logreg_kernel.step_reserve_bytes(BATCH, WIDTH, 8)]
    # one batch in flight, its two temporaries, two width² accumulators
    assert logreg_kernel.step_reserve_bytes(100, 21, 4) == 4 * (
        3 * 100 * 21 + 2 * 21 * 21)


# -- against the in-memory program and float64 --------------------------------


@pytest.mark.parametrize("batch_rows", [0, BATCH], ids=["auto", "batchRows"])
def test_the_streamed_fit_is_the_in_memory_one(batch_rows):
    """The auto-sized batches (≈128 MiB: one batch here) and the Param's:
    the same Newton steps, the same answer as the in-memory program."""
    x, y = _rows(seed=5)
    streamed = _estimator(batchRows=batch_rows).fit(_factory(x, y))
    oneshot = _estimator().fit(x, y)
    assert streamed.n_iter_ == oneshot.n_iter_ == 6
    np.testing.assert_allclose(streamed.coefficients, oneshot.coefficients,
                               rtol=0, atol=1e-10)
    assert streamed.intercept == pytest.approx(oneshot.intercept, abs=1e-10)
    ingest = streamed.fit_report_.extra["ingest"]
    assert ingest["host_batches"] == 6 * (BATCHES if batch_rows else 1)


def test_a_float32_fit_is_newton_to_float32_rounding():
    x, y = _rows(seed=6)
    model = _fit(x.astype(np.float32), y.astype(np.float32), dtype="float32")
    _assert_plain_newton(model, x.astype(np.float32).astype(np.float64), y,
                         atol=2e-5)


def test_without_an_intercept_the_slot_stays_zero():
    x, y = _rows(seed=7)
    model = _fit(x, y, fitIntercept=False)
    assert model.intercept == 0.0
    _assert_plain_newton(model, x, y, fit_intercept=False)
    oneshot = _estimator(fitIntercept=False).fit(x, y)
    np.testing.assert_allclose(model.coefficients, oneshot.coefficients,
                               rtol=0, atol=1e-10)


def test_tol_stops_the_steps():
    x, y = _rows()
    model = _fit(x, y, maxIter=50, tol=1e-6)
    newton = model.fit_report_.extra["newton"]
    assert 1 < model.n_iter_ < 50 and newton["steps"] == model.n_iter_
    assert newton["final_step_max"] <= 1e-6
    _assert_plain_newton(model, x, y)


def test_labels_are_checked_on_the_first_walk():
    x, y = _rows()
    with pytest.raises(ValueError, match="0/1 labels"):
        _fit(x, 2 * y)  # two classes, not 0 and 1
    # more than two classes: Spark's family "auto" goes multinomial; the
    # third class first shows in the masked tail's valid rows
    three = y.copy()
    three[ROWS - 5] = 2.0
    model = _fit(x, three)
    assert model.num_classes == 3


# -- one body: every input, weighted or not -----------------------------------


def _weights(seed: int = 12):
    """Integer weights 0..3: some rows weigh nothing, the padded tail's
    rows among them."""
    return np.random.default_rng(seed).integers(0, 4, ROWS).astype(float)


def _frame(x, y, weights=None):
    frame = as_vector_frame(x, "features").with_column("label", y.tolist())
    return frame if weights is None else frame.with_column(
        "w", weights.tolist())


def _fit_form(form, x, y, weights=None, **params):
    est = _estimator(**params)
    if weights is not None:
        est.setWeightCol("w")
    if form == "array":
        return est.fit(x, y)
    if form == "frame":
        return est.fit(_frame(x, y, weights))
    return est.fit(_factory(x, y))


@pytest.mark.parametrize("fit_intercept", [True, False],
                         ids=["intercept", "no-intercept"])
@pytest.mark.parametrize("use_xla", [True, False], ids=["xla", "host"])
@pytest.mark.parametrize("form, weighted", [
    ("array", False), ("frame", False), ("callable", False),
    ("frame", True)], ids=["array", "frame", "callable", "weighted-frame"])
def test_every_input_is_plain_newton_on_the_one_loop(form, weighted,
                                                     use_xla, fit_intercept):
    """An array, a frame and a chunk callable of the same rows, and a frame
    with weightCol: each the same Newton — the weighted MLE where weighted
    — on both planes, with and without an intercept."""
    x, y = _rows(seed=10)
    weights = _weights() if weighted else None
    model = _fit_form(form, x, y, weights, useXlaDot=use_xla,
                      fitIntercept=fit_intercept)
    assert model.n_iter_ == 6
    coef, b = _numpy_newton(x, y, LAM, 6, fit_intercept, weights)
    np.testing.assert_allclose(model.coefficients, coef, rtol=0, atol=1e-9)
    assert model.intercept == pytest.approx(b, abs=1e-9)
    if use_xla:  # the streamed loop's report, whatever the input
        ingest = model.fit_report_.extra["ingest"]
        assert ingest["passes"] == 6
        assert model.fit_report_.extra["newton"]["steps"] == 6


@pytest.mark.parametrize("use_xla", [True, False], ids=["xla", "host"])
def test_integer_weights_are_the_rows_repeated(use_xla):
    x, y = _rows(seed=13)
    weights = _weights(seed=14)
    weighted = _fit_form("frame", x, y, weights, useXlaDot=use_xla)
    times = weights.astype(int)
    repeated = _estimator(useXlaDot=use_xla).fit(np.repeat(x, times, 0),
                                                 np.repeat(y, times))
    np.testing.assert_allclose(weighted.coefficients, repeated.coefficients,
                               rtol=0, atol=1e-10)
    assert weighted.intercept == pytest.approx(repeated.intercept, abs=1e-10)


def test_a_weighted_frame_walks_the_loop():
    """``rows_put`` counts every row that crosses, padding too; the rows
    the fit reports are those with a weight."""
    x, y = _rows()
    weights = _weights()
    model = _fit_form("frame", x, y, weights, maxIter=2)
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["batches"] == 2 * BATCHES  # nothing kept on the CPU
    assert ingest["rows_put"] == 2 * BATCHES * BATCH
    assert model.fit_report_.rows == np.count_nonzero(weights) < ROWS
    assert model.fit_report_.extra["newton"]["batches_put_again"] == BATCHES


@pytest.mark.parametrize("form", ["array", "frame", "callable"])
def test_an_unweighted_fit_puts_no_mask_or_a_padding_mask(monkeypatch, form):
    masks = []
    real = streaming.IngestTrace.put

    def put(self, batch, mask, dtype):
        masks.append(mask)
        return real(self, batch, mask, dtype)

    monkeypatch.setattr(streaming.IngestTrace, "put", put)
    x, y = _rows()
    _fit_form(form, x, y, maxIter=1)
    assert len(masks) == BATCHES
    assert all(m is None for m in masks[:-1])
    assert masks[-1].dtype == bool and masks[-1].sum() == ROWS % BATCH
    masks.clear()
    _fit_form("frame", x, y, _weights(), maxIter=1)
    assert [m.dtype.kind for m in masks] == ["f"] * BATCHES


def test_a_weighted_callable_is_still_refused():
    x, y = _rows()
    with pytest.raises(ValueError, match="weightCol"):
        _estimator().setWeightCol("w").fit(_factory(x, y))


# -- the [X | y] source ---------------------------------------------------------


def _aligned(x, y, rows: int = 2 * BATCH):
    """The rows as (X, y) chunks of ``rows`` rows: a multiple of BATCH,
    so every full batch is a row slice of one chunk."""
    return lambda: ((x[i:i + rows], y[i:i + rows])
                    for i in range(0, len(y), rows))


@pytest.mark.parametrize("chunk_rows, viewed, slices", [
    (2 * BATCH, BATCHES - 1, 1), (CHUNK, 2, 1), (2 * BATCH, BATCHES - 1, 2)],
    ids=["aligned", "uneven", "aligned-two-slices"])
def test_the_join_of_x_and_y_is_on_the_chip_for_a_slice_of_one_chunk(
        monkeypatch, chunk_rows, viewed, slices):
    """A full batch that is a row slice of one (X, y) chunk crosses as its
    X and y and is joined on the chip: viewed, no host copy. Batches
    assembled from two chunks, and the padded tail, are still joined by
    their one host copy. Cut into two slices for the link (the benchmark
    cell's 1.57 GB batches), each slice is a put joined on the chip."""
    if slices > 1:
        monkeypatch.setattr(streaming, "PUT_BYTES_MAX",
                            BATCH * WIDTH * 8 // slices)
    x, y = _rows()
    factory = _aligned(x, y, chunk_rows)
    model = _estimator(maxIter=1).fit(factory)
    ingest = model.fit_report_.extra["ingest"]
    chunks = -(-ROWS // chunk_rows)
    copied = BATCHES - viewed  # the tail among them
    assert ingest["chunks"] == ingest["chunks_viewed"] == chunks
    assert (ingest["batches_viewed"], ingest["batches_copied"]) == (
        viewed, copied)
    assert ingest["bytes_reblocked"] == (
        (copied - 1) * BATCH + ROWS % BATCH) * WIDTH * 8
    # each slice of a viewed batch is one put joined on the chip, counted
    # on its chip too
    assert ingest["batches"] == slices * BATCHES
    assert ingest["batches_joined_on_chip"] == slices * viewed
    assert ingest["per_chip"][0]["batches_joined_on_chip"] == slices * viewed
    assert ingest["bytes_put"] == BATCHES * BATCH * WIDTH * 8
    assert model.fit_timings_[streaming.PHASE_NEXT_PART["copy"]] > 0
    # and it leaves through the reports' ring as the others do
    ring = obs.recent_fit_reports(1, "logreg")[-1].extra["ingest"]
    assert ring["batches_joined_on_chip"] == slices * viewed
    _assert_plain_newton(model, x, y)


def _blocks(x, y):
    return linear_regression._zip_xy((x, y))


@pytest.mark.parametrize("x_dtype, y_dtype, put_dtype", [
    (np.float32, np.float64, np.float32),  # X a view, y cast
    (np.float64, np.float64, np.float64),  # both views
    (np.float32, np.int64, np.float64),    # both cast
], ids=["x-view-y-cast", "both-views", "both-cast"])
def test_a_put_of_column_blocks_is_the_joined_batch_bit_for_bit(
        monkeypatch, x_dtype, y_dtype, put_dtype):
    rng = np.random.default_rng(21)
    x = (rng.normal(size=(BATCH, N)) / 3).astype(x_dtype)
    y = rng.integers(0, 2, size=BATCH).astype(y_dtype)
    if np.dtype(y_dtype).kind == "f":
        y = y + np.asarray(0.1, y_dtype)  # rounds when cast to float32
    put_from = []
    real = streaming.jax.device_put

    def device_put(value, device=None, **kwargs):
        put_from.append(value)
        return real(value, device, **kwargs)

    monkeypatch.setattr(streaming.jax, "device_put", device_put)
    ingest = streaming.IngestTrace()
    _, x_dev, m_dev = ingest.put(_blocks(x, y), None, put_dtype)
    assert m_dev is None
    want = np.column_stack([x, y]).astype(put_dtype)
    got = np.asarray(x_dev)
    assert got.dtype == want.dtype and got.shape == (BATCH, N + 1)
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    # X crosses from the caller's rows where its dtype is the put's, the
    # label column beside it
    assert [a.shape for a in put_from] == [(BATCH, N), (BATCH, 1)]
    assert np.shares_memory(put_from[0], x) == (x_dtype == put_dtype)


def test_a_put_of_column_blocks_is_one_put(monkeypatch):
    """One window slot, one landing (timed on the blocks, not on the
    join), one ``batches``, the joined rows' bytes; a plain batch is
    joined nowhere."""
    for watcher in list(streaming._WATCHERS.values()):
        # earlier fits' landings are not this put's
        assert watcher.fence().wait(timeout=20)
    seen = []

    def landing(put):
        seen.append(put)
        jax.block_until_ready(put)

    monkeypatch.setattr(streaming, "landing_of", landing)
    x, y = _rows()
    x, y = x[:BATCH].astype(np.float32), y[:BATCH]
    ingest = streaming.IngestTrace()
    c, x_dev, _ = ingest.put(_blocks(x, y), None, np.float32)
    assert len(ingest.chips[c].in_flight) == 1
    ingest.release()
    ingest.all_landed()
    counters = ingest.counters
    assert counters["batches"] == counters["batches_joined_on_chip"] == 1
    assert counters["per_chip"][c]["landings"] == 1
    assert counters["bytes_put"] == BATCH * WIDTH * 4
    assert counters["rows_put"] == BATCH
    assert ingest.itemsize == 4
    (put,) = seen
    assert isinstance(put, tuple) and len(put) == 2
    assert all(block is not x_dev for block in put)
    assert [block.shape for block in put] == [(BATCH, N), (BATCH, 1)]

    plain = streaming.IngestTrace()
    plain.put(np.column_stack([x, y]), None, np.float32)
    assert plain.counters["batches_joined_on_chip"] == 0
    assert plain.counters["bytes_put"] == BATCH * WIDTH * 4


@pytest.mark.parametrize("chunk_rows", [2 * BATCH, CHUNK],
                         ids=["aligned", "uneven"])
def test_the_chips_join_fits_what_the_hosts_join_fits(monkeypatch,
                                                       chunk_rows):
    """The same rows with every batch joined on the host (the chunks fed
    as joined [X | y] arrays): the same device batches, so the same fit
    bit for bit."""
    x, y = _rows(seed=9)
    on_chip = _estimator().fit(_aligned(x, y, chunk_rows))
    assert on_chip.fit_report_.extra["ingest"]["batches_joined_on_chip"]
    monkeypatch.setattr(linear_regression, "_zip_xy",
                        lambda chunk: np.column_stack(chunk))
    on_host = _estimator().fit(_aligned(x, y, chunk_rows))
    ingest = on_host.fit_report_.extra["ingest"]
    assert ingest["batches_joined_on_chip"] == 0
    assert on_chip.n_iter_ == on_host.n_iter_ == 6
    np.testing.assert_array_equal(on_chip.coefficients, on_host.coefficients)
    assert on_chip.intercept == on_host.intercept


def test_a_walk_nobody_traces_gets_joined_host_arrays():
    """After a traced walk (views of the blocks) the same source walked
    by a NumPy consumer gets joined arrays: the walk took its trace."""
    x, y = _rows()
    source = linear_regression._streaming_xy_source(_aligned(x, y), None,
                                                    BATCH)
    traced = [b for b, _ in streaming.IngestTrace().batches(source)]
    assert sum(isinstance(b, ColumnBlocks) for b in traced) == BATCHES - 1
    assert source.trace is None
    untraced = list(source.batches())
    assert not any(isinstance(b, ColumnBlocks) for b, _ in untraced)
    z = np.concatenate([b if m is None else b[m] for b, m in untraced])
    np.testing.assert_array_equal(z, np.column_stack([x, y]))


@pytest.mark.parametrize("batch_rows", [0, 64], ids=["auto", "batchRows"])
def test_the_xy_source_honours_batch_rows(batch_rows):
    x, y = _rows()
    source = linear_regression._streaming_xy_source(_factory(x, y), None,
                                                    batch_rows)
    assert source.n_features == WIDTH
    assert source.batch_rows == (batch_rows or auto_batch_rows(WIDTH))
    z = np.concatenate([np.vstack([b if m is None else b[m]])
                        for b, m in source.batches()])
    np.testing.assert_array_equal(z, np.column_stack([x, y]))


def test_column_blocks_cast_to_the_promoted_dtype():
    x = np.arange(12, dtype=np.int16).reshape(4, 3)
    y = np.array([0.5, 1.5, 2.5, 3.5], dtype=np.float32)
    chunk = linear_regression._zip_xy((x, y))
    assert isinstance(chunk, ColumnBlocks)
    assert chunk.shape == (4, 4) and chunk.dtype == np.float32
    out = np.empty((2, 4), np.float32)
    chunk[1:3].write_into(out)
    np.testing.assert_array_equal(out, [[3, 4, 5, 1.5], [6, 7, 8, 2.5]])
    with pytest.raises(ValueError, match="labels length"):
        linear_regression._zip_xy((x, y[:3]))


# -- spans and counters --------------------------------------------------------


def test_the_fit_emits_the_newton_spans_in_order(monkeypatch):
    x, y = _rows()
    model = _fit(x, y, EVERYTHING, monkeypatch, maxIter=3)
    names = [e.name for e in sorted(
        obs_spans.get_recorder().events(model.fit_report_.trace_id),
        key=lambda e: (e.ts_us, -e.dur_us))
        if e.name in lr_module.NEWTON_SPANS]
    walk = [lr_module.SPAN_DISPATCH] * BATCHES + [lr_module.SPAN_SYNC]
    assert names == ([lr_module.SPAN_INIT] + walk
                     + ([lr_module.SPAN_STEP] + walk) * 2)
    timings = model.fit_timings_
    for phase in (lr_module.PHASE_INIT, lr_module.PHASE_STEP,
                  lr_module.PHASE_DISPATCH, lr_module.PHASE_SYNC,
                  streaming.PHASE_PUT, streaming.PHASE_NEXT):
        assert timings[phase] > 0, phase
    newton = model.fit_report_.extra["newton"]
    assert set(newton) == {"steps", "batches_from_kept", "batches_put_again",
                           "sync_seconds", "solve_seconds", "final_step_max"}
    assert newton["steps"] == 3
    assert newton["sync_seconds"] == timings[lr_module.PHASE_SYNC]
    assert 0 < newton["solve_seconds"] <= newton["sync_seconds"]
    assert newton["final_step_max"] > 0
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["batches"] == BATCHES  # one crossing
    assert ingest["bytes_put"] == BATCHES * BATCH * WIDTH * 8


def _load(relpath: str):
    path = os.path.join(ROOT, "benchmarks", *relpath.split("/"))
    spec = importlib.util.spec_from_file_location(
        relpath.replace("/", "_")[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_benchmarks_newton_names_are_what_the_program_emits():
    work = _load("work/newton.py")
    assert work.SPANS == lr_module.NEWTON_SPANS
    assert not set(work.SPANS) & set(streaming.STREAM_SPANS)
    spans = _load("work/spans.py")
    assert not set(work.SPANS) & set(spans.PROGRAM_SPANS)
    # the roofline reader finds the step's program by its traced name, and
    # not the finishing program's
    traced = "jit_" + logreg_kernel.update_logreg_stats.__name__
    assert any(p in traced for p in work.PROGRAMS)
    assert not any(p in "jit_" + logreg_kernel.newton_step.__name__
                   for p in work.PROGRAMS)


def test_no_roofline_reads_the_join_program():
    """The chip's join of X and y is a copy, not a work module's program:
    its traced name matches none of their ``PROGRAMS``."""
    traced = "jit_" + streaming.join_columns.__name__
    for name in ("gram", "lloyd", "newton", "collective"):
        programs = _load(f"work/{name}.py").PROGRAMS
        assert not any(p in traced for p in programs), name


def test_the_in_memory_program_and_the_step_share_the_solve(monkeypatch):
    """One assembly and Cholesky step, ``newton_delta``, in the mesh fit's
    one-program iterations and in the streamed step."""
    calls = []
    real = logreg_kernel.newton_delta

    def delta(*args, **kwargs):
        calls.append(args[1].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(logreg_kernel, "newton_delta", delta)
    x, y = _rows(seed=8)
    x, n = x[:100, :5], 5  # shapes no other test traces: both trace here
    jax.jit(lambda x, y: logreg_kernel.newton_iterations(
        x, y, None, 0.0, True, 2, 1e-8))(x, y[:100])
    carry = logreg_kernel.update_logreg_stats(
        logreg_kernel.init_logreg_carry(n, np.float64),
        np.column_stack([x, y[:100]]), np.zeros(n), np.float64(0.0))
    logreg_kernel.newton_step(carry, np.zeros(n), np.float64(0.0), LAM)
    assert calls == [(n + 1,), (n + 1,)]


# -- the weighted Gram's upper panels ---------------------------------------

PANEL = logreg_kernel.GRAM_PANEL


@pytest.mark.parametrize("n, masked", [
    (PANEL // 6, False), (PANEL, False), (3 * PANEL + 7, False),
    (3 * PANEL + 7, True)], ids=["under", "one-panel", "off-grid", "masked"])
def test_the_weighted_gram_is_the_full_product_mirrored(n, masked):
    rng = np.random.default_rng(n)
    rows = 96
    x = rng.normal(size=(rows, n)).astype(np.float32)
    s = rng.uniform(0.01, 0.25, size=rows).astype(np.float32)
    h = np.asarray(jax.jit(logreg_kernel.weighted_gram)(x, s))
    full = np.asarray(jax.jit(lambda x, s: jax.lax.dot_general(
        x, x * s[:, None], (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST))(x, s))
    panels = logreg_kernel.gram_panels(n)
    assert h.shape == (n, n) and h.dtype == np.float32
    np.testing.assert_allclose(h, full, rtol=0,
                               atol=32 * np.finfo(np.float32).eps
                               * np.abs(full).max())
    np.testing.assert_array_equal(h, h.T)  # bit for bit
    assert len(panels) == -(-n // PANEL)
    assert sum(w for _, w in panels) == n
    # the panels tile the columns in order, each PANEL wide but the last
    assert [s for s, _ in panels] == list(range(0, n, PANEL))
    assert all(w == PANEL for _, w in panels[:-1])

    # a batch through the step's program: padded rows (garbage behind a
    # false mask) add nothing, and the carry keeps its shapes
    z = np.column_stack([x, (rng.uniform(size=rows) < 0.5)]).astype(
        np.float32)
    coef = (0.05 * rng.normal(size=n)).astype(np.float32)
    b = np.float32(0.2)
    mask, keep = None, rows
    if masked:
        keep = rows - 17
        mask = np.arange(rows) < keep
        z[keep:] = 1e3 * rng.normal(size=(rows - keep, n + 1))
    carry = logreg_kernel.init_logreg_carry(n, np.float32)
    shapes = [a.shape for a in carry]
    carry = logreg_kernel.update_logreg_stats(carry, z, coef, b, mask)
    assert [a.shape for a in carry] == shapes
    only = logreg_kernel.update_logreg_stats(
        logreg_kernel.init_logreg_carry(n, np.float32), z[:keep], coef, b)
    for got, want in zip(carry, only):
        want = np.asarray(want)
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=0,
            atol=32 * np.finfo(np.float32).eps * max(np.abs(want).max(), 1))
    np.testing.assert_array_equal(np.asarray(carry[1]),
                                  np.asarray(carry[1]).T)
    assert float(carry[5]) == keep


def test_a_wide_streamed_fit_reports_its_panels():
    """Past one panel's width the step's Gram is two upper panels, one
    product each, and the streamed fit is still plain Newton."""
    n = PANEL + 16
    rng = np.random.default_rng(11)
    x = rng.normal(size=(ROWS, n))
    y = (x @ rng.normal(size=n) / np.sqrt(n) + 0.3 * rng.normal(size=ROWS)
         > 0).astype(np.float64)
    assert len(logreg_kernel.gram_panels(n)) == 2
    jaxpr = jax.make_jaxpr(logreg_kernel.weighted_gram)(x, np.ones(ROWS))
    assert len(_dot_precisions(jaxpr.jaxpr)) == 2
    streamed = _estimator(maxIter=3).fit(_factory(x, y))
    oneshot = _estimator(maxIter=3).fit(x, y)
    assert streamed.n_iter_ == oneshot.n_iter_ == 3
    np.testing.assert_allclose(streamed.coefficients, oneshot.coefficients,
                               rtol=0, atol=1e-10)
    assert streamed.intercept == pytest.approx(oneshot.intercept, abs=1e-10)
    _assert_plain_newton(streamed, x, y)


def _dot_precisions(jaxpr) -> list:
    """The precision of every ``dot_general`` in a jaxpr, its inner jaxprs
    (a jitted call's body) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)  # a ClosedJaxpr's
            if hasattr(inner, "eqns"):
                found += _dot_precisions(inner)
    return found


@pytest.mark.parametrize("program", ["weighted_gram", "update_logreg_stats"])
def test_every_product_of_the_step_is_at_highest(program):
    """A CPU dot is float32 at any precision setting, so no result of the
    suite would see a panel fall to one bfloat16 pass: read the programs'
    products instead."""
    n, rows = 3 * PANEL + 7, 16
    x = np.zeros((rows, n), np.float32)
    if program == "weighted_gram":
        jaxpr = jax.make_jaxpr(logreg_kernel.weighted_gram)(
            x, np.ones(rows, np.float32))
        products = len(logreg_kernel.gram_panels(n))
    else:
        jaxpr = jax.make_jaxpr(logreg_kernel.update_logreg_stats)(
            logreg_kernel.init_logreg_carry(n, np.float32),
            np.zeros((rows, n + 1), np.float32), np.zeros(n, np.float32),
            np.float32(0.0))
        products = len(logreg_kernel.gram_panels(n)) + 2  # X·w and Xᵀr
    precisions = _dot_precisions(jaxpr.jaxpr)
    assert len(precisions) == products
    highest = jax.lax.Precision.HIGHEST
    assert all(p == (highest, highest) for p in precisions), precisions
