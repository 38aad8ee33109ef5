"""Spans, sub-phases and counters of the streamed PCA fit (CPU).

One fit per source form — a callable (two passes where its rows refuse
the shifted Gram, as i.i.d. batches of 64 rows do; one walk where they
accept it, the ``*_accepted`` forms: ``tests/test_streaming_shift.py``), a
one-shot iterator (one pass), and each of them with a ragged tail — then
one test per (form, assertion group). The name guards at the end hold the benchmark's
lists (``benchmarks/work/spans.py``, ``benchmarks/work/gram.py``) against
what the program emits, so a rename fails here instead of turning a
per-layer metric into ``null``.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shift_rows import mirrored_pairs, verdict

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.models import pca as pca_module
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.obs.report import current_fit
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.ops.covariance import covariance_from_stats
from spark_rapids_ml_tpu.utils.timing import PhaseTimer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, BATCH, K = 24, 64, 3
# rows per chunk: whole batches, or a tail of 40 rows after three batches
FORMS = {
    "callable": ("callable", (128, 128)),
    "iterator": ("iterator", (128, 128)),
    "ragged_callable": ("callable", (128, 104)),
    "ragged_iterator": ("iterator", (128, 104)),
    # the same rows as mirrored pairs: the two-pass fit's shift is accepted
    "callable_accepted": ("callable", (128, 128)),
    "ragged_callable_accepted": ("callable", (128, 104)),
}
SUB_PHASES = ("covariance/next", "covariance/put", "covariance/dispatch",
              "covariance/sync")


def _bench_module(relpath: str):
    path = os.path.join(ROOT, "benchmarks", relpath)
    spec = importlib.util.spec_from_file_location(
        "spans_test_" + relpath.replace("/", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _chunks(rows: tuple, seed: int = 7, accepted: bool = False) -> list:
    rng = np.random.default_rng(seed)
    chunks = [(rng.normal(size=(r, N)) + 0.5).astype(np.float32)
              for r in rows]
    return mirrored_pairs(chunks, 0.5) if accepted else chunks


def _dataset(input_form: str, chunks: list):
    if input_form == "callable":
        return lambda: list(chunks)
    return iter(chunks)


def _by_hand(input_form: str, chunks: list):
    """The bare accumulate functions on the same batches in the same
    order: what ``stream_covariance`` did before it had spans."""
    source = BatchSource(_dataset(input_form, chunks), batch_rows=BATCH)

    def put(batch, mask):
        return (jax.device_put(np.asarray(batch, dtype=jnp.float32), None),
                None if mask is None else jax.device_put(mask, None))

    if source.reiterable:
        mstats = streaming.MeanStats(jnp.zeros((N,), jnp.float32),
                                     jnp.zeros((), jnp.int32))
        gram, shift = jnp.zeros((N, N), jnp.float32), None
        for batch, mask in source.batches():
            x, m = put(batch, mask)
            mstats = streaming.update_mean_stats(mstats, x, m)
            if shift is None:
                shift = mstats.col_sum / mstats.count
            gram = streaming.update_centered_gram_auto(gram, x, shift, m)
        mean = mstats.col_sum / mstats.count
        gram, ratio = streaming.recentre_gram(gram, mstats.col_sum,
                                              mstats.count, shift, mean)
        if not float(ratio) <= streaming.SHIFT_RATIO_MAX:
            gram = jnp.zeros((N, N), jnp.float32)
            for batch, mask in source.batches():
                x, m = put(batch, mask)
                gram = streaming.update_centered_gram_auto(gram, x, mean, m)
        return gram / jnp.maximum(mstats.count - 1, 1), mean, mstats.count
    stats = streaming.init_stats(N)
    for batch, mask in source.batches():
        stats = streaming.update_stats_auto(stats, *put(batch, mask))
    cov = covariance_from_stats(stats.gram, stats.col_sum, stats.count)
    return cov, stats.col_sum / stats.count, stats.count


@pytest.fixture(scope="module", params=sorted(FORMS))
def fitted(request):
    input_form, rows = FORMS[request.param]
    accepted = request.param.endswith("_accepted")
    chunks = _chunks(rows, accepted=accepted)
    model = PCA().setK(K).set("batchRows", BATCH).set("dtype", "float32").fit(
        _dataset(input_form, chunks))
    report = model.fit_report_
    # the main thread's spans: the landing watchers' run beside them on
    # lines of their own (``tests/test_streaming_landing.py`` holds those)
    events = sorted((e for e in obs_spans.get_recorder().events(
        report.trace_id) if not e.name.startswith(streaming.SPAN_LANDING)),
        key=lambda e: (e.ts_us, -e.dur_us))
    two_pass = input_form == "callable"
    if two_pass:  # the rows are on the side of the verdict they are meant for
        assert verdict(report.extra["ingest"]) == (accepted,
                                                   1 if accepted else 2)
    return {"form": request.param, "input_form": input_form, "rows": rows,
            "chunks": chunks, "model": model, "report": report,
            "events": events, "two_pass": two_pass, "accepted": accepted,
            "batches_per_pass": -(-sum(rows) // BATCH),
            "ragged": sum(rows) % BATCH != 0}


def _expected_names(f) -> list:
    """The fit's spans in order. Inside each ``stream:next`` the source's
    own: one ``stream:next/read`` a chunk pulled and read (both chunks of
    128 rows arrive in the ``next`` for batches 1 and 3, the exhausted pull
    in the last; a one-shot source has read its first chunk before the
    fit's stream starts, to learn the width) and one ``stream:next/copy``
    for the padded tail (in that last ``next`` too)."""
    per_pass = f["batches_per_pass"]
    read, copy = (streaming.SPAN_NEXT_PART[p] for p in ("read", "copy"))

    def walk(pass_span, paths):
        names = [pass_span]
        for i, path in enumerate(paths):
            names += [streaming.SPAN_NEXT]
            if i == 2 or (i == 0 and f["two_pass"]):
                names += [read]
            if i == 3 and f["ragged"]:  # the exhausted pull, then the pad
                names += [read, copy]
            names += [streaming.SPAN_PUT, streaming.SPAN_ACCUMULATE[path]]
        names += [streaming.SPAN_NEXT]  # the exhausted next()
        return names if f["ragged"] else names + [read]

    # on the CPU every Gram goes the XLA way, the masked tail included
    names = [pca_module.SPAN_FIT, pca_module.SPAN_STREAMED_COV]
    if f["two_pass"]:
        # pass 1: each batch's mean step, then its Gram step about the
        # first batch's mean; the one host read is the verdict's, and only
        # a refused shift walks the rows again
        mean, gram = (streaming.SPAN_ACCUMULATE[p] for p in ("mean", "xla"))
        for name in walk(streaming.SPAN_PASS_MEAN, ["mean"] * per_pass):
            names += [name, gram] if name == mean else [name]
        names += [streaming.SPAN_SYNC_COUNT]
        if not f["accepted"]:
            names += walk(streaming.SPAN_PASS_GRAM, ["xla"] * per_pass)
    else:
        names += walk(streaming.SPAN_PASS_STATS, ["xla"] * per_pass)
    return names + [streaming.SPAN_SYNC_COV, pca_module.SPAN_XLA_EIGH,
                    pca_module.SPAN_FETCH]


def _parent_of(event, events):
    """The shortest other span that contains ``event`` in time."""
    lo, hi = event.ts_us, event.ts_us + event.dur_us
    around = [e for e in events if e is not event
              and e.ts_us <= lo and e.ts_us + e.dur_us >= hi]
    return min(around, key=lambda e: e.dur_us).name if around else None


def test_span_names_in_order(fitted):
    assert [e.name for e in fitted["events"]] == _expected_names(fitted)


def test_span_nesting(fitted):
    events = fitted["events"]
    passes = (streaming.SPAN_PASS_MEAN, streaming.SPAN_PASS_GRAM,
              streaming.SPAN_PASS_STATS)
    in_pass = (streaming.SPAN_NEXT, streaming.SPAN_PUT,
               *streaming.SPAN_ACCUMULATE.values())
    in_cov = passes + (streaming.SPAN_SYNC_COUNT, streaming.SPAN_SYNC_COV)
    for e in events:
        parent = _parent_of(e, events)
        if e.name in streaming.SPAN_NEXT_PART.values():
            assert parent == streaming.SPAN_NEXT, (e.name, parent)
        elif e.name in in_pass:
            assert parent in passes, (e.name, parent)
        elif e.name in in_cov:
            assert parent == pca_module.SPAN_STREAMED_COV, (e.name, parent)
        elif e.name == pca_module.SPAN_FIT:
            assert parent is None
        else:  # streamed cov, xla eigh, fit:fetch
            assert parent == pca_module.SPAN_FIT, (e.name, parent)


def test_sub_phases_are_in_fit_timings(fitted):
    t = fitted["model"].fit_timings_
    for key in SUB_PHASES + ("fetch", "covariance", "solve"):
        assert key in t and t[key] >= 0.0, key
    # the sub-phases lie inside `covariance`, `fetch` beside it
    assert sum(t[key] for key in SUB_PHASES) <= t["covariance"] + 1e-3
    assert t["covariance/put"] > 0 and t["covariance/dispatch"] > 0
    # the report's phases carry them too
    assert set(t) <= set(fitted["report"].phases)
    # a stage's timer starts before its span and stops after it, so a
    # phase's seconds are its spans' seconds and a little more per stage:
    # opening and filing the span, and whatever a loaded host (six test
    # workers) takes the thread off the core for in between
    seconds, stages = {}, {}
    for e in fitted["events"]:
        seconds[e.name] = seconds.get(e.name, 0.0) + e.dur_us / 1e6
        stages[e.name] = stages.get(e.name, 0) + 1
    grain, slack = 1e-5, 0.05  # a span's µs rounding; seconds a stage
    for span, phase in ((streaming.SPAN_PUT, "covariance/put"),
                        (pca_module.SPAN_FETCH, "fetch")):
        assert seconds[span] - grain * stages[span] <= t[phase] \
            <= seconds[span] + slack * stages[span], (span, phase)


def test_ingest_counters(fitted):
    ingest = fitted["report"].extra["ingest"]
    passes = 2 if fitted["two_pass"] and not fitted["accepted"] else 1
    per_pass = fitted["batches_per_pass"]
    assert ingest["passes"] == passes
    assert ingest["batches"] == passes * per_pass
    # padding crosses too: every batch has the full shape
    assert ingest["rows_put"] == passes * per_pass * BATCH
    assert ingest["bytes_put"] == passes * per_pass * BATCH * N * 4
    calls = ingest["accumulate_calls"]
    assert calls["mean"] == (per_pass if fitted["two_pass"] else 0)
    # the ragged tail is masked, and a masked batch goes the XLA way at
    # the full padded shape (ROADMAP M3) — as does everything on the CPU
    # (as dispatched: a refused shift's steps of pass 1 and of pass 2)
    assert calls["xla"] == passes * per_pass and calls["pallas"] == 0
    assert 0 < ingest["put_seconds_max"] <= \
        fitted["model"].fit_timings_["covariance/put"]
    assert 0 < ingest["sync_seconds_max"] <= \
        fitted["model"].fit_timings_["covariance/sync"]
    assert ingest["hbm_bytes_in_use"] == {}  # no memory_stats() on the CPU


def test_report_sizes_a_streamed_input(fitted):
    report = fitted["report"]
    rows = sum(fitted["rows"])
    assert report.rows == rows  # without the padding
    assert report.features == N
    assert report.bytes_processed == rows * N * 4  # once, whatever crossed
    assert report.programs_compiled >= 0 and report.programs_fetched >= 0


def test_result_is_bit_equal_to_the_bare_accumulate_calls(fitted):
    source = BatchSource(_dataset(fitted["input_form"], fitted["chunks"]),
                         batch_rows=BATCH)
    timer = PhaseTimer()
    cov, mean, count = streaming.stream_covariance(
        source, ingest=streaming.IngestTrace(timer))
    want_cov, want_mean, want_count = _by_hand(fitted["input_form"],
                                               fitted["chunks"])
    assert np.array_equal(np.asarray(cov), np.asarray(want_cov))
    assert np.array_equal(np.asarray(mean), np.asarray(want_mean))
    assert int(count) == int(want_count) == sum(fitted["rows"])
    assert set(SUB_PHASES) - {"covariance/sync"} <= set(timer.as_dict())
    # and the model of the fit is what that covariance solves to
    assert np.array_equal(fitted["model"].mean,
                          np.asarray(want_mean, dtype=np.float64))


@pytest.mark.parametrize("traffic,accepted", [
    ("fit-1pass", False), ("fit-2pass", False), ("fit-2pass", True)],
    ids=["fit-1pass", "fit-2pass", "fit-2pass-accepted"])
def test_bytes_put_are_the_bytes_the_benchmark_reckons(traffic, accepted):
    """``benchmarks/run.py`` hands the readers ``crossings x rows x n x 4``
    as the bytes put; the program counts the same from the arrays — on the
    CPU, where nothing is kept, and where the rows refuse the shifted Gram
    (pass 2 then puts them again). Rows that accept it cross once whatever
    is kept, as they do on the chip."""
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           traffic + ".json")) as f:
        spec = json.load(f)
    chunk_rows, n_chunks = 2 * BATCH, spec["chunks_per_fit"]
    chunks = _chunks((chunk_rows,) * n_chunks, seed=3, accepted=accepted)
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").fit(_dataset(spec["input_form"], chunks))
    ingest = model.fit_report_.extra["ingest"]
    itemsize = np.dtype("float32").itemsize
    crossings = 1 if accepted else spec["crossings"]
    assert ingest["bytes_put"] == (
        crossings * chunk_rows * n_chunks * N * itemsize)
    assert ingest["passes"] == crossings
    assert ingest["rows_put"] == crossings * chunk_rows * n_chunks


def test_hbm_is_read_at_the_boundaries_only(monkeypatch):
    reads = []

    def stats(device):
        reads.append(device)
        return {"bytes_in_use": 1000 + len(reads), "peak_bytes_in_use": 5000}

    monkeypatch.setattr(streaming, "device_memory_stats", stats)
    two = PCA().setK(K).set("batchRows", BATCH).fit(
        _dataset("callable", _chunks((128, 104)))).fit_report_
    # the two-pass fit reads once more, before its first put: the budget of
    # the batches it may keep (``keep_budget_bytes``; no ``bytes_limit`` in
    # these stats, so it keeps none)
    # (the verdict's read comes before a refused shift's pass 2)
    assert list(two.extra["ingest"]["hbm_bytes_in_use"].items()) == [
        ("pass/mean:end", 1002), ("sync/count", 1003),
        ("pass/gram:end", 1004), ("sync/cov", 1005),
        ("solve:start", 1006), ("solve:end", 1007)]
    assert two.extra["ingest"]["batches_kept"] == 0
    assert verdict(two.extra["ingest"]) == (False, 2)
    accepted = PCA().setK(K).set("batchRows", BATCH).fit(
        _dataset("callable", _chunks((128, 104), accepted=True))).fit_report_
    assert list(accepted.extra["ingest"]["hbm_bytes_in_use"]) == [
        "pass/mean:end", "sync/count", "sync/cov", "solve:start",
        "solve:end"]
    one = PCA().setK(K).set("batchRows", BATCH).fit(
        _dataset("iterator", _chunks((128, 104)))).fit_report_
    assert list(one.extra["ingest"]["hbm_bytes_in_use"]) == [
        "pass/stats:end", "sync/cov", "solve:start", "solve:end"]
    assert len(reads) == 17  # one a boundary, one the budget, none a batch


def test_counters_outside_a_fit_go_nowhere():
    """``stream_covariance`` called bare still traces and counts, and
    leaves nothing behind on the shared no-fit context."""
    source = BatchSource(_chunks((128,)), batch_rows=BATCH)
    ingest = streaming.IngestTrace()
    streaming.stream_covariance(source, ingest=ingest)
    assert ingest.counters["batches"] == 4 and ingest.counters["passes"] == 2
    assert current_fit().extra == {} and current_fit().rows is None


# -- name guards --------------------------------------------------------------


def test_the_benchmarks_span_list_is_what_the_program_emits():
    bench_spans = _bench_module("work/spans.py")
    program = ((pca_module.SPAN_FIT, pca_module.SPAN_STREAMED_COV)
               + streaming.STREAM_SPANS
               + (pca_module.SPAN_XLA_EIGH, pca_module.SPAN_FETCH))
    assert len(set(program)) == len(program)
    listed = [s for s in bench_spans.PROGRAM_SPANS
              if s != bench_spans.BENCH_SPAN]
    assert sorted(listed) == sorted(program)
    assert set(bench_spans.COARSE) == {
        bench_spans.BENCH_SPAN, pca_module.SPAN_FIT,
        pca_module.SPAN_STREAMED_COV}
    assert pca_module.SPAN_FIT == f"fit:{PCA.fit.__obs_instrumented__}"


def test_the_crossings_names_are_listed_apart_by_the_benchmark():
    """The landing spans, ``covariance/crossing`` and the Spark front's
    ``stage:action`` are in neither ``STREAM_SPANS`` nor the accepted
    readers' ``PROGRAM_SPANS`` (so those read what they read);
    ``benchmarks/work/crossing.py`` lists them, and a rename fails here."""
    from spark_rapids_ml_tpu.spark import estimator as front

    crossing = _bench_module("work/crossing.py")
    bench_spans = _bench_module("work/spans.py")
    assert crossing.LANDING_PREFIX == streaming.SPAN_LANDING + "/"
    assert crossing.CROSSING_PHASE == streaming.PHASE_CROSSING
    assert crossing.ACTION_SPAN == front.SPAN_ACTION
    assert crossing.ACTION_PHASE == front.PHASE_ACTION
    stage = _bench_module("work/stage.py")
    for listed in (streaming.STREAM_SPANS, bench_spans.PROGRAM_SPANS,
                   tuple(stage.SPANS.values())):
        assert not any(name.startswith(streaming.SPAN_LANDING)
                       for name in listed)
        assert front.SPAN_ACTION not in listed
    assert set(crossing.LANDING_COUNTERS) == set(streaming.LANDING_COUNTERS)


def test_every_span_constant_was_seen_in_some_fit():
    """The constants are not only declared: between them the CPU fits of
    this file emitted every one but the Pallas accumulate (TPU only; in the
    ring all the same where a file that stubs the platform ran earlier in
    this worker)."""
    seen = {e.name for e in obs_spans.get_recorder().events()}
    for form, rows in FORMS.values():
        PCA().setK(K).set("batchRows", BATCH).fit(
            _dataset(form, _chunks(rows)))
    seen |= {e.name for e in obs_spans.get_recorder().events()}
    assert set(streaming.STREAM_SPANS) - seen <= {
        streaming.SPAN_ACCUMULATE["pallas"]}


@pytest.mark.parametrize("name", [
    "update_mean_stats", "update_centered_gram",
    "_update_centered_gram_fused_blocked", "update_stats",
    "_update_stats_fused_blocked"])
def test_accumulate_programs_are_found_by_the_roofline_reader(name):
    """``accumulate_roofline`` finds the kernels by substring on the traced
    module name ``jit_<__name__>``; every tracked function the streamed fit
    can dispatch has to match one of ``work/gram.py:PROGRAMS``."""
    gram = _bench_module("work/gram.py")
    from spark_rapids_ml_tpu.obs.xprof import TrackedJit

    fn = getattr(streaming, name)
    assert isinstance(fn, TrackedJit)
    traced = "jit_" + fn.__name__
    assert any(p in traced for p in gram.PROGRAMS), traced


def test_no_other_tracked_accumulate_function_hides_in_streaming():
    from spark_rapids_ml_tpu.obs.xprof import TrackedJit
    from spark_rapids_ml_tpu.ops import covariance

    tracked = {name for module in (streaming, covariance)
               for name, value in vars(module).items()
               if isinstance(value, TrackedJit)}
    assert tracked == {
        "update_mean_stats", "update_centered_gram",
        "_update_centered_gram_fused_blocked", "update_stats",
        "_update_stats_fused_blocked", "finalize_stats",
        # no accumulate program: one elementwise pass over the n x n sum
        # (``tests/test_streaming_shift.py`` holds its name off the list)
        "recentre_gram",
        # no accumulate program either: the chip's join of a labelled
        # slice's X and y (``tests/test_logreg_streamed.py`` holds its name
        # off every work module's list)
        "join_columns"}
