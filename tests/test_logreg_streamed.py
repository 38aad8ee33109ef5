"""Binary LogisticRegression on the streamed loop (CPU): the labelled rows
cross once through ``ops.streaming.IngestTrace``, each Newton step walks
the batches kept on the chip and puts the rest again, and solves its
system on the device.

The CPU reports no ``memory_stats()``, so nothing is kept here unless a
test stands in for the chip through the seam ``streaming.keep_budget_bytes``
(as ``tests/test_kmeans_streamed.py`` does for Lloyd): all, some or none of
the batches kept, the fit has to be plain Newton from w = 0 — a NumPy
float64 Newton over the same rows, step for step — and the in-memory
program's answer. The benchmark's names (``benchmarks/work/newton.py``)
are held against what the program emits.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from spark_rapids_ml_tpu import LogisticRegression
from spark_rapids_ml_tpu.data.batches import ColumnBlocks, auto_batch_rows
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


def _numpy_newton(x, y, lam, steps, fit_intercept=True):
    """``steps`` float64 Newton steps from w = 0 on Spark's objective."""
    n = x.shape[1]
    w = np.zeros(n + 1)
    for _ in range(steps):
        p = 1.0 / (1.0 + np.exp(-(x @ w[:n] + w[n])))
        r, s = p - y, p * (1.0 - p)
        g = np.append(x.T @ r / len(y) + lam * w[:n], r.mean())
        h = np.zeros((n + 1, n + 1))
        h[:n, :n] = x.T @ (x * s[:, None]) / len(y) + lam * np.eye(n)
        h[:n, n] = h[n, :n] = x.T @ s / len(y)
        h[n, n] = s.mean()
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


# -- the [X | y] source ---------------------------------------------------------


def test_the_join_of_x_and_y_is_one_host_copy_a_batch():
    x, y = _rows()
    model = _fit(x, y, maxIter=1)
    ingest = model.fit_report_.extra["ingest"]
    # every batch is written once from the chunks' X and y, none copied
    # whole first: the chunks are the caller's arrays
    assert ingest["chunks"] == ingest["chunks_viewed"] == -(-ROWS // CHUNK)
    assert ingest["batches_copied"] == BATCHES and not ingest[
        "batches_viewed"]
    assert ingest["bytes_reblocked"] == (
        (BATCHES - 1) * BATCH + ROWS % BATCH) * WIDTH * 8
    assert model.fit_timings_[streaming.PHASE_NEXT_PART["copy"]] > 0


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


def test_the_in_memory_program_and_the_step_share_the_solve(monkeypatch):
    """One assembly and Cholesky step, ``newton_delta``, in both."""
    calls = []
    real = logreg_kernel.newton_delta

    def delta(*args, **kwargs):
        calls.append(args[1].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(logreg_kernel, "newton_delta", delta)
    x, y = _rows(seed=8)
    x, n = x[:100, :5], 5  # shapes no other test traces: both trace here
    logreg_kernel.logreg_fit_kernel(x, y[:100], max_iter=2)
    carry = logreg_kernel.update_logreg_stats(
        logreg_kernel.init_logreg_carry(n, np.float64),
        np.column_stack([x, y[:100]]), np.zeros(n), np.float64(0.0))
    logreg_kernel.newton_step(carry, np.zeros(n), np.float64(0.0), LAM)
    assert calls == [(n + 1,), (n + 1,)]
