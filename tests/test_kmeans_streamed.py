"""KMeans on the streamed loop (CPU): the rows cross once through
``ops.streaming.IngestTrace``, the Lloyd steps walk the batches kept on
the chip and put the rest again.

The CPU reports no ``memory_stats()``, so nothing is kept here unless a
test stands in for the chip through the seam ``streaming.keep_budget_bytes``
(as ``tests/test_streaming_keep.py`` does for PCA): all, some or none of the
batches kept, the fit has to be plain Lloyd from its own initial centres —
a NumPy float64 Lloyd over the same rows, step for step. The benchmark's
names (``benchmarks/work/lloyd.py``) are held against what the program
emits.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from spark_rapids_ml_tpu import KMeans, PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.models import kmeans as kmeans_module
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import kmeans_kernel, streaming

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, K, BATCH = 8, 4, 128
CHUNK = 173  # uneven against BATCH: re-blocked batches and a masked tail
ROWS = 600  # four whole batches and a tail of 88 rows
BATCHES = -(-ROWS // BATCH)
EVERYTHING = 1 << 40


def _rows(seed: int = 3) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centres = 6.0 * rng.normal(size=(K, N))
    return centres[rng.integers(0, K, ROWS)] + rng.normal(size=(ROWS, N))


def _factory(x: np.ndarray):
    return lambda: (x[i:i + CHUNK] for i in range(0, len(x), CHUNK))


def _fit(x, budget=None, monkeypatch=None, **params):
    if budget is not None:
        monkeypatch.setattr(streaming, "keep_budget_bytes",
                            lambda *a, **k: budget)
    est = KMeans().setK(K).setSeed(5).set("batchRows", BATCH)
    for name, value in params.items():
        est.set(name, value)
    return est.fit(_factory(x))


def _plain_lloyd(x, centres, steps):
    """(centres, cost) after ``steps`` NumPy float64 Lloyd steps from
    ``centres`` (an empty cluster keeps its centre), the cost under the
    last centres."""
    c = np.array(centres, dtype=np.float64)

    def d2(c):
        return ((x[:, None, :] - c[None]) ** 2).sum(-1)

    for _ in range(steps):
        nearest = d2(c).argmin(1)
        for j in range(len(c)):
            if np.any(nearest == j):
                c[j] = x[nearest == j].mean(0)
    return c, d2(c).min(1).sum()


def _assert_plain_lloyd(model, x):
    want, cost = _plain_lloyd(x, model.initial_centers_, model.n_iter_)
    np.testing.assert_allclose(model.cluster_centers, want, rtol=0,
                               atol=1e-9)
    assert model.training_cost_ == pytest.approx(cost, rel=1e-9)


# -- the loop at every keep level ---------------------------------------------


@pytest.mark.parametrize("kept", [BATCHES, 2, 0], ids=["all", "some", "none"])
def test_the_loop_is_plain_lloyd_at_every_keep_level(monkeypatch, kept):
    x = _rows()
    batch_nbytes = BATCH * N * 8  # the suite runs float64 (x64)
    model = _fit(x, kept * batch_nbytes, monkeypatch, maxIter=6, tol=0.0)
    _assert_plain_lloyd(model, x)
    ingest = model.fit_report_.extra["ingest"]
    lloyd = model.fit_report_.extra["lloyd"]
    walks = 1 + lloyd["steps"] + 1  # the first walk, the steps, the cost
    assert ingest["batches_kept"] == kept
    assert ingest["passes"] == walks
    # every batch crosses once; a batch not kept crosses again every walk
    assert ingest["batches"] == kept + (BATCHES - kept) * walks
    assert lloyd["batches_from_kept"] == kept * (walks - 1)
    assert lloyd["batches_put_again"] == (BATCHES - kept) * (walks - 1)
    assert model.fit_report_.rows == ROWS


def test_keeping_changes_nothing_in_the_answer(monkeypatch):
    x = _rows(seed=4)
    models = [_fit(x, budget, monkeypatch, maxIter=5, tol=0.0)
              for budget in (EVERYTHING, 3 * BATCH * N * 8, 0)]
    for other in models[1:]:
        np.testing.assert_array_equal(other.cluster_centers,
                                      models[0].cluster_centers)
        assert other.training_cost_ == models[0].training_cost_


def test_the_budget_is_the_steps_own_reserve(monkeypatch):
    asked = []

    def rule(device, reserve_nbytes):
        asked.append(reserve_nbytes)
        return 0

    monkeypatch.setattr(streaming, "keep_budget_bytes", rule)
    _fit(_rows(), maxIter=2)
    itemsize = 8
    assert asked == [kmeans_kernel.step_reserve_bytes(BATCH, K, N, itemsize)]
    # three batches, the (rows, k) distances and one-hot, four (k, n)
    assert kmeans_kernel.step_reserve_bytes(100, 10, 20, 4) == 4 * (
        3 * 100 * 20 + 2 * 100 * 10 + 4 * 10 * 20)


def test_a_free_chip_keeps_what_fits_beside_the_reserve():
    class Device:
        def memory_stats(self):
            return {"bytes_limit": 10_000, "bytes_in_use": 1_000}

    assert streaming.keep_budget_bytes(Device(), 2_500) == 6_500
    # the Gram loop's reserve: three batches, three n*n
    assert streaming.keep_budget_bytes(
        Device(), streaming.gram_reserve_bytes(100, 10)) == 9_000 - 330


# -- steps and initial centres ------------------------------------------------


def test_a_tol_no_centre_meets_runs_every_step(monkeypatch):
    # overlapping clusters: three steps from random rows do not stand still
    rng = np.random.default_rng(8)
    x = rng.normal(size=(ROWS, N))
    model = _fit(x, EVERYTHING, monkeypatch, maxIter=3, tol=1e-20,
                 initMode="random")
    assert model.n_iter_ == 3
    assert model.fit_report_.extra["lloyd"]["steps"] == 3
    _assert_plain_lloyd(model, x)


def test_a_fixed_point_stops_the_steps_at_any_tol(monkeypatch):
    """Spark's rule: converged when no centre moved more than tol — a
    step that moves nothing meets tol 1e-20 too."""
    x = _rows()
    model = _fit(x, EVERYTHING, monkeypatch, maxIter=50, tol=1e-20)
    assert 1 < model.n_iter_ < 50
    again = _plain_lloyd(x, model.cluster_centers, 1)[0]
    np.testing.assert_allclose(again, model.cluster_centers, atol=1e-12)


@pytest.mark.parametrize("kept", [BATCHES, 2, 0], ids=["all", "some", "none"])
def test_random_init_is_k_distinct_rows_the_same_for_the_same_seed(
        monkeypatch, kept):
    x = _rows()
    budget = kept * BATCH * N * 8
    first = _fit(x, budget, monkeypatch, initMode="random", maxIter=1)
    again = _fit(x, budget, monkeypatch, initMode="random", maxIter=1)
    init = first.initial_centers_
    rows = [int(np.flatnonzero((x == c).all(1))[0]) for c in init]
    assert len(set(rows)) == K  # k distinct rows of the data
    np.testing.assert_array_equal(again.initial_centers_, init)
    other = _fit(x, budget, monkeypatch, initMode="random", maxIter=1,
                 seed=6)
    assert not np.array_equal(other.initial_centers_, init)


def test_random_init_does_not_depend_on_what_is_kept(monkeypatch):
    x = _rows()
    inits = [_fit(x, kept * BATCH * N * 8, monkeypatch, initMode="random",
                  maxIter=1).initial_centers_ for kept in (BATCHES, 2, 0)]
    for init in inits[1:]:
        np.testing.assert_array_equal(init, inits[0])


def test_random_rows_are_drawn_uniformly():
    """Algorithm R a batch at a time: over many seeds every row is drawn
    about k / rows of the time, whatever batch it lies in."""
    rows, k, draws = 40, 4, 4000
    hits = np.zeros(rows)
    x = np.arange(rows, dtype=np.float64)[:, None]
    for seed in range(draws):
        init = kmeans_module._RandomRows(k, 1, np.random.default_rng(seed))
        for start in range(0, rows, 12):
            init.see(x[start:start + 12], None)
        picked = init.host_centers()[:, 0].astype(int)
        assert len(set(picked)) == k
        hits[picked] += 1
    assert np.all(np.abs(hits / draws - k / rows) < 0.03)


def test_random_init_on_an_in_memory_matrix():
    x = _rows()
    inits = []
    for use_xla in (True, False):
        model = (KMeans().setK(K).setSeed(2).set("initMode", "random")
                 .setUseXlaDot(use_xla).setMaxIter(2).fit(x))
        found = [np.flatnonzero((x == c).all(1)) for c in
                 model.initial_centers_]
        assert all(len(f) == 1 for f in found)
        assert len({int(f[0]) for f in found}) == K
        inits.append(model.initial_centers_)
    # one draw: the device and the host path start from the same rows
    np.testing.assert_array_equal(inits[0], inits[1])


def test_a_one_shot_iterator_still_raises():
    with pytest.raises(ValueError, match="re-iterable"):
        KMeans().setK(K).fit(iter([_rows()]))


def test_k_over_the_rows_raises():
    with pytest.raises(ValueError, match="at most the number of rows"):
        KMeans().setK(ROWS + 1).set("initMode", "random").fit(
            _factory(_rows()))


# -- spans and counters --------------------------------------------------------


def test_the_fit_emits_the_lloyd_spans_in_order(monkeypatch):
    model = _fit(_rows(), EVERYTHING, monkeypatch, maxIter=3, tol=0.0)
    names = [e.name for e in sorted(
        obs_spans.get_recorder().events(model.fit_report_.trace_id),
        key=lambda e: (e.ts_us, -e.dur_us))
        if e.name in kmeans_module.LLOYD_SPANS]
    steps = model.n_iter_
    step = ([kmeans_module.SPAN_STEP]
            + [kmeans_module.SPAN_DISPATCH] * BATCHES
            + [kmeans_module.SPAN_SYNC])
    assert names == ([kmeans_module.SPAN_INIT] + step * steps
                     + [kmeans_module.SPAN_COST]
                     + [kmeans_module.SPAN_DISPATCH] * BATCHES)
    timings = model.fit_timings_
    for phase in (kmeans_module.PHASE_INIT, kmeans_module.PHASE_DISPATCH,
                  kmeans_module.PHASE_SYNC, kmeans_module.PHASE_COST,
                  streaming.PHASE_PUT, streaming.PHASE_NEXT):
        assert timings[phase] > 0, phase
    lloyd = model.fit_report_.extra["lloyd"]
    assert lloyd["steps"] == steps
    assert lloyd["sync_seconds"] == timings[kmeans_module.PHASE_SYNC]
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["batches"] == BATCHES  # one crossing
    assert ingest["bytes_put"] == BATCHES * BATCH * N * 8


def test_the_benchmarks_lloyd_names_are_what_the_program_emits():
    path = os.path.join(ROOT, "benchmarks", "work", "lloyd.py")
    spec = importlib.util.spec_from_file_location("lloyd_work", path)
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    assert work.SPANS == kmeans_module.LLOYD_SPANS
    assert not set(work.SPANS) & set(streaming.STREAM_SPANS)
    path = os.path.join(ROOT, "benchmarks", "work", "spans.py")
    spec = importlib.util.spec_from_file_location("spans_work", path)
    spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(spans)
    assert not set(work.SPANS) & set(spans.PROGRAM_SPANS)
    # the roofline reader finds the step's program by its traced name
    assert isinstance(kmeans_kernel.update_cluster_stats,
                      type(streaming.update_stats))
    traced = "jit_" + kmeans_kernel.update_cluster_stats.__name__
    assert any(p in traced for p in work.PROGRAMS)
    assert not any(p in "jit_" + f.__name__ for p in work.PROGRAMS
                   for f in (kmeans_kernel.next_centers,
                             kmeans_kernel.take_rows))


# -- PCA's replay drains as before ---------------------------------------------


def test_replay_drains_for_pca_and_keeps_for_lloyd(monkeypatch):
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda *a, **k: EVERYTHING)
    x = _rows()
    source = BatchSource(_factory(x), batch_rows=BATCH)
    ingest = streaming.IngestTrace()
    ingest.allow_keep(0)
    with ingest.walk(streaming.SPAN_PASS_STATS):
        for batch, mask in ingest.batches(source):
            assert ingest.keep(*ingest.put(batch, mask, np.float64))
    for _ in range(2):  # a Lloyd step's walk leaves every batch kept
        assert len(list(ingest.replay(source, np.float64, drain=False))) \
            == BATCHES
        assert len(ingest.kept) == BATCHES
    handed = []
    for item in ingest.replay(source, np.float64):  # pass 2 of a Gram
        handed.append(item)
        assert len(ingest.kept) == BATCHES - len(handed)
    assert len(handed) == BATCHES and not ingest.kept
    assert ingest.counters["batches"] == BATCHES  # nothing put again
    ingest.release()


def test_a_two_pass_pca_still_lets_its_kept_batches_go(monkeypatch):
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda *a, **k: EVERYTHING)
    seen = []
    real = streaming.IngestTrace.replay

    def replay(self, *args, **kwargs):
        seen.append(kwargs.get("drain", True))
        return real(self, *args, **kwargs)

    monkeypatch.setattr(streaming.IngestTrace, "replay", replay)
    monkeypatch.setattr(streaming, "SHIFT_RATIO_MAX", -1.0)  # pass 2 runs
    model = PCA().setK(2).set("batchRows", BATCH).fit(_factory(_rows()))
    assert seen == [True]
    assert model.fit_report_.extra["ingest"]["passes"] == 2
