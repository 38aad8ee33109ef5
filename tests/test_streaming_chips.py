"""The streamed PCA fit over several chips (CPU: ``conftest.py`` gives eight
host devices).

``stream_covariance`` takes the chips: host batches are dealt to them whole
and in turn, each chip sums its own with the one-chip programs and keeps
its own batches under its own budget, and the chips meet in two
all-reduces (one in a one-pass fit). What must hold: the answer is the
plain two-pass covariance of all the rows whatever the number of chips,
every row is counted once on exactly one chip, the chips' parts add up to
the whole fit's, and one chip is the loop it always was, bit for bit. The
name guards at the end hold ``benchmarks/work/collective.py`` against what
the program emits.
"""

from __future__ import annotations

import importlib.util
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
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.parallel import data_mesh
from spark_rapids_ml_tpu.parallel import mesh as pm
from spark_rapids_ml_tpu.parallel.streaming import (
    distributed_streaming_pca_fit,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, K = 64, 3
WIDTHS = {"aligned128": 128, "unaligned98": 98}
# rows per chunk → batches per pass: eight whole ones (two a chip on four
# chips), seven with a masked tail of 40 rows (one chip gets fewer), three
# (on four chips one gets none)
ROWS = {"whole8": (256, 256), "tail7": (256, 168), "few3": (128, 64)}
FORMS = ("callable", "iterator")
EVERYTHING = 1 << 40


def _bench_module(relpath: str):
    path = os.path.join(ROOT, "benchmarks", relpath)
    spec = importlib.util.spec_from_file_location(
        "chips_test_" + relpath.replace("/", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _chunks(n: int, rows: tuple, seed: int = 13,
            shift: str = "refused") -> list:
    rng = np.random.default_rng(seed)
    # |mean| >> sigma in some columns, and a mean that drifts from chunk to
    # chunk: a chip's own mean is then far from the mean of all rows — and
    # a chip's first batch no stand-in for it: every two-pass fit of these
    # rows refuses its shifted Gram and runs pass 2. ``accepted``: the same
    # rows as mirrored pairs about the columns' centres (no drift left).
    centre = 20.0 * (np.arange(n) % 3)
    chunks = [(rng.normal(size=(r, n)) * np.linspace(2.0, 0.5, n)
               + centre + 3.0 * i).astype(np.float32)
              for i, r in enumerate(rows)]
    return mirrored_pairs(chunks, centre) if shift == "accepted" else chunks


def _dataset(form: str, chunks: list):
    return (lambda: list(chunks)) if form == "callable" else iter(chunks)


def _plain(chunks: list):
    """The textbook two-pass covariance in float64 on the host."""
    x = np.concatenate(chunks).astype(np.float64)
    mean = x.mean(axis=0)
    xc = x - mean
    return xc.T @ xc / (x.shape[0] - 1), mean, x.shape[0]


def _stream(dataset, chips, **kwargs):
    device = None if chips is None else tuple(jax.local_devices()[:chips])
    ingest = streaming.IngestTrace(device=device)
    cov, mean, count = streaming.stream_covariance(
        BatchSource(dataset, batch_rows=BATCH), dtype=jnp.float32,
        ingest=ingest, **kwargs)
    return (np.asarray(cov), np.asarray(mean), int(count)), ingest


def _close(got, want, cov_tol=2e-5, mean_tol=2e-6):
    cov, mean, count = want
    assert got[2] == count
    assert np.max(np.abs(got[1] - mean)) <= mean_tol * np.max(np.abs(mean))
    assert np.max(np.abs(got[0] - cov)) <= cov_tol * np.max(np.abs(cov))


CASES = [pytest.param(chips, form, n, rows, id=f"{chips}chips-{form}-{w}-{r}")
         for chips in (1, 2, 4) for form in FORMS
         for w, n in WIDTHS.items() for r, rows in ROWS.items()]


@pytest.mark.parametrize("chips,form,n,rows", CASES)
def test_any_number_of_chips_gives_the_plain_covariance(chips, form, n, rows):
    chunks = _chunks(n, rows)
    got, ingest = _stream(_dataset(form, chunks), chips)
    tol = 2e-5 if form == "callable" else 2e-3  # one pass: raw moments
    _close(got, _plain(chunks), cov_tol=tol)
    one, _ = _stream(_dataset(form, chunks), 1)
    _close(got, one, cov_tol=tol)
    c = ingest.counters
    assert c["chips"] == chips == len(c["per_chip"])
    per_pass = -(-sum(rows) // BATCH)
    # every row once, on exactly one chip; batch i went to chip i % chips
    assert sum(chip["rows"] for chip in c["per_chip"]) == sum(rows)
    assert [chip["rows_put"] for chip in c["per_chip"]] == [
        (2 if form == "callable" else 1) * BATCH
        * len(range(i, per_pass, chips)) for i in range(chips)]
    assert sum(chip["bytes_put"] for chip in c["per_chip"]) == c["bytes_put"]
    assert len({chip["device"] for chip in c["per_chip"]}) == chips


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("n,rows", [(128, ROWS["tail7"]), (98, ROWS["few3"])])
def test_one_chip_named_is_the_loop_with_none_named(form, n, rows):
    """``device=(chip,)`` and ``device=None``: same programs, same order."""
    chunks = _chunks(n, rows)
    named, ingest = _stream(_dataset(form, chunks), 1)
    bare, _ = _stream(_dataset(form, chunks), None)
    assert np.array_equal(named[0], bare[0])
    assert np.array_equal(named[1], bare[1]) and named[2] == bare[2]
    assert ingest.counters["collective_bytes"] == {}
    assert streaming.PHASE_COLLECTIVE not in ingest.timer.as_dict()


@pytest.mark.parametrize("chips", [2, 4])
@pytest.mark.parametrize("n,rows", [(128, ROWS["tail7"]), (98, ROWS["few3"])])
def test_the_chips_parts_add_up_to_the_whole_fit(monkeypatch, chips, n, rows):
    """The share test: what each chip hands to the two all-reduces — its
    column sums and count, then its Gram — adds up to the one-chip fit's,
    with nothing counted twice."""
    chunks = _chunks(n, rows)
    seen = {}
    collective_mean, collective_sum = (streaming.collective_mean,
                                       streaming.collective_sum)

    def spy_mean(ingest, mstats):
        seen["col_sums"] = [np.asarray(s.col_sum, np.float64) for s in mstats]
        seen["counts"] = [int(s.count) for s in mstats]
        return collective_mean(ingest, mstats)

    def spy_sum(ingest, parts):
        seen["grams"] = [np.asarray(p[0], np.float64) for p in parts]
        return collective_sum(ingest, parts)

    monkeypatch.setattr(streaming, "collective_mean", spy_mean)
    monkeypatch.setattr(streaming, "collective_sum", spy_sum)
    got, ingest = _stream(_dataset("callable", chunks), chips)
    cov, mean, count = _plain(chunks)
    x = np.concatenate(chunks).astype(np.float64)
    assert sum(seen["counts"]) == count == got[2]
    assert seen["counts"] == [chip["rows"]
                              for chip in ingest.counters["per_chip"]]
    np.testing.assert_allclose(sum(seen["col_sums"]), x.sum(axis=0),
                               rtol=2e-6)
    whole = sum(seen["grams"])
    np.testing.assert_allclose(whole / (count - 1), cov,
                               atol=2e-5 * np.max(np.abs(cov)))
    # each chip's Gram is of its own rows about the mean of ALL rows
    per_pass = -(-count // BATCH)
    for i, gram in enumerate(seen["grams"]):
        own = np.concatenate(
            [x[b * BATCH:(b + 1) * BATCH] for b in range(i, per_pass, chips)]
            or [np.zeros((0, n))]) - mean
        np.testing.assert_allclose(gram, own.T @ own,
                                   atol=2e-5 * np.max(np.abs(whole)))


def _budgets(monkeypatch, by_chip: dict) -> None:
    """Stand in for the chips: each device's budget from ``by_chip``
    (position among the local devices → bytes)."""
    local = jax.local_devices()

    def rule(device, batch_nbytes, gram_nbytes):
        return by_chip[local.index(device)]

    monkeypatch.setattr(streaming, "keep_budget_bytes", rule)


@pytest.mark.parametrize("n,rows", [(128, ROWS["whole8"]), (98, ROWS["tail7"])])
def test_each_chip_keeps_under_its_own_budget(monkeypatch, n, rows):
    chunks = _chunks(n, rows)
    nothing_kept, _ = _stream(_dataset("callable", chunks), 4)
    batch = BATCH * n * 4
    _budgets(monkeypatch, {0: EVERYTHING, 1: batch * 3 // 2, 2: 0,
                           3: EVERYTHING})
    calls = []

    def factory():
        calls.append(1)
        return list(chunks)

    got, ingest = _stream(factory, 4)
    # the same arrays through the same programs in the same order per chip
    assert np.array_equal(got[0], nothing_kept[0])
    assert np.array_equal(got[1], nothing_kept[1])
    per_pass = -(-sum(rows) // BATCH)
    dealt = [len(range(i, per_pass, 4)) for i in range(4)]
    chips = ingest.counters["per_chip"]
    assert [c["batches_kept"] for c in chips] == [dealt[0], 1, 0, dealt[3]]
    assert [c["bytes_kept"] for c in chips] == [
        dealt[0] * batch, batch, 0, dealt[3] * batch]
    assert [c["keep_budget_bytes"] for c in chips] == [
        EVERYTHING, batch * 3 // 2, 0, EVERYTHING]
    # a kept batch does not cross again: chip i put its share once, and
    # what it did not keep a second time
    assert [c["bytes_put"] for c in chips] == [
        (2 * d - c["batches_kept"]) * batch for d, c in zip(dealt, chips)]
    total = ingest.counters
    assert total["batches_kept"] == sum(c["batches_kept"] for c in chips)
    assert total["keep_budget_bytes"] == 2 * EVERYTHING + batch * 3 // 2
    assert total["batches"] == 2 * per_pass - total["batches_kept"]
    assert len(calls) == 4  # probe, peek, pass 1, pass 2 (some were not kept)
    assert not ingest.kept

    _budgets(monkeypatch, dict.fromkeys(range(4), EVERYTHING))
    calls.clear()
    got, ingest = _stream(factory, 4)
    assert np.array_equal(got[0], nothing_kept[0])
    assert ingest.counters["batches"] == per_pass  # one crossing
    assert len(calls) == 3  # pass 2 did not walk the source


@pytest.mark.parametrize("chips", [2, 4])
def test_a_stale_factory_still_raises_over_several_chips(monkeypatch, chips):
    chunks = _chunks(98, ROWS["tail7"])
    state = {"fresh": True}
    source = BatchSource(
        lambda: iter(chunks if state["fresh"] else chunks[1:]),
        batch_rows=BATCH)
    assert source.reiterable
    batches = source.batches

    def batches_then_stale():
        yield from batches()
        state["fresh"] = False  # pass 2 gets an iterator somebody has used

    monkeypatch.setattr(source, "batches", batches_then_stale)
    ingest = streaming.IngestTrace(device=tuple(jax.local_devices()[:chips]))
    with pytest.raises(RuntimeError, match="FRESH iterator"):
        streaming.stream_covariance(source, ingest=ingest)


# -- the collectives: spans, counters, bytes ----------------------------------


def _fit(chips: int, form: str, chunks: list):
    return PCA().setK(K).set("batchRows", BATCH).set("dtype", "float32").set(
        "numDevices", chips).fit(_dataset(form, chunks))


def _span_names(model) -> list:
    """The main thread's spans in order (the landing watchers' are on lines
    of their own: ``tests/test_streaming_landing.py``)."""
    events = sorted(
        obs_spans.get_recorder().events(model.fit_report_.trace_id),
        key=lambda e: (e.ts_us, -e.dur_us))
    return [e.name for e in events
            if not e.name.startswith(streaming.SPAN_LANDING)]


@pytest.mark.parametrize("shift", ["refused", "accepted"])
@pytest.mark.parametrize("chips", [2, 4])
def test_two_pass_fit_dispatches_two_collectives(chips, shift):
    n = 128
    model = _fit(chips, "callable", _chunks(n, ROWS["tail7"], shift=shift))
    refused = shift == "refused"
    ingest = model.fit_report_.extra["ingest"]
    assert verdict(ingest) == (not refused, 2 if refused else 1)
    names = _span_names(model)
    mean_at = names.index(streaming.SPAN_COLLECTIVE["mean"])
    gram_at = names.index(streaming.SPAN_COLLECTIVE["gram"])
    assert names.count(streaming.SPAN_COLLECTIVE["mean"]) == 1
    # (b) once for the Grams summed in pass 1 and re-centred, and once more
    # for pass 2's where the rows refused those
    assert names.count(streaming.SPAN_COLLECTIVE["gram"]) == 1 + refused
    # (a) after pass 1, (b) before the first host read, the verdict's
    assert (names.index(streaming.SPAN_PASS_MEAN) < mean_at < gram_at
            < names.index(streaming.SPAN_SYNC_COUNT)
            < names.index(streaming.SPAN_SYNC_COV))
    if refused:
        again = len(names) - 1 - names[::-1].index(
            streaming.SPAN_COLLECTIVE["gram"])
        assert (names.index(streaming.SPAN_SYNC_COUNT)
                < names.index(streaming.SPAN_PASS_GRAM) < again
                < names.index(streaming.SPAN_SYNC_COV))
    else:
        assert streaming.SPAN_PASS_GRAM not in names
    # everything else is the one-chip fit's list
    one = _span_names(_fit(1, "callable",
                           _chunks(n, ROWS["tail7"], shift=shift)))
    assert [s for s in names
            if s not in streaming.SPAN_COLLECTIVE.values()] == one
    collective = _bench_module("work/collective.py")
    assert ingest["collective_bytes"] == {
        "mean": collective.mean_bytes(n),
        "gram": (1 + refused) * collective.gram_bytes(n)}
    assert model.fit_report_.collectives["all_reduce"] == {
        "count": 2 + refused,
        "bytes": collective.mean_bytes(n)
        + (1 + refused) * collective.gram_bytes(n)}
    t = model.fit_timings_
    assert 0 < t[streaming.PHASE_COLLECTIVE] < t["covariance"]
    assert t[collective.PHASE] == t[streaming.PHASE_COLLECTIVE]


@pytest.mark.parametrize("chips", [2, 4])
def test_one_pass_fit_dispatches_the_second_collective_only(chips):
    n = 98
    model = _fit(chips, "iterator", _chunks(n, ROWS["tail7"]))
    names = _span_names(model)
    assert names.count(streaming.SPAN_COLLECTIVE["gram"]) == 1
    assert streaming.SPAN_COLLECTIVE["mean"] not in names
    assert (names.index(streaming.SPAN_PASS_STATS)
            < names.index(streaming.SPAN_COLLECTIVE["gram"])
            < names.index(streaming.SPAN_SYNC_COV))
    # the column sums and the count ride along with the Gram
    assert model.fit_report_.extra["ingest"]["collective_bytes"] == {
        "gram": n * n * 4 + n * 4 + 4}
    assert model.fit_report_.collectives["all_reduce"]["count"] == 1


def test_one_chip_fit_has_no_collective():
    model = _fit(1, "callable", _chunks(98, ROWS["few3"]))
    assert not set(_span_names(model)) & set(
        streaming.SPAN_COLLECTIVE.values())
    assert streaming.PHASE_COLLECTIVE not in model.fit_timings_
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["chips"] == 1 and ingest["collective_bytes"] == {}
    assert model.fit_report_.collectives == {}


def test_hbm_is_read_on_every_chip_at_the_boundaries(monkeypatch):
    local = jax.local_devices()
    reads = []

    def stats(device):
        reads.append(device)
        return {"bytes_in_use": 1000 * (local.index(device) + 1) + len(reads)}

    monkeypatch.setattr(streaming, "device_memory_stats", stats)
    report = _fit(2, "callable", _chunks(98, ROWS["few3"])).fit_report_
    ingest = report.extra["ingest"]
    assert verdict(ingest) == (False, 2)  # the read comes before pass 2
    boundaries = ["pass/mean:end", "sync/count", "pass/gram:end", "sync/cov",
                  "solve:start", "solve:end"]
    for chip in ingest["per_chip"]:
        assert list(chip["hbm_bytes_in_use"]) == boundaries
    # the fit's own key holds the fullest chip's reading
    assert ingest["hbm_bytes_in_use"] == ingest["per_chip"][1][
        "hbm_bytes_in_use"]
    # two budgets, then two reads a boundary, none a batch
    assert len(reads) == 2 + 2 * len(boundaries)


# -- the estimator's door -------------------------------------------------------


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("chips", [2, 4])
def test_pca_fit_over_several_chips_agrees_with_one(chips, form):
    chunks = _chunks(128, ROWS["tail7"])
    one = _fit(1, form, chunks)
    model = _fit(chips, form, chunks)
    assert isinstance(model, pca_module.PCAModel)
    assert model.getNumDevices() == chips
    np.testing.assert_allclose(model.mean, one.mean, rtol=2e-6)
    np.testing.assert_allclose(model.explained_variance,
                               one.explained_variance, rtol=1e-4)
    np.testing.assert_allclose(np.abs(model.pc), np.abs(one.pc), atol=2e-3)
    assert model.fit_report_.rows == sum(ROWS["tail7"])
    assert model.fit_report_.extra["ingest"]["chips"] == chips


@pytest.mark.parametrize("form", FORMS)
def test_the_default_is_one_chip_whatever_the_host_has(form):
    chunks = _chunks(98, ROWS["tail7"])
    assert len(jax.local_devices()) > 1
    default = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").fit(_dataset(form, chunks))
    assert default.getNumDevices() == 1
    one = _fit(1, form, chunks)
    assert np.array_equal(default.pc, one.pc)
    assert np.array_equal(default.explained_variance, one.explained_variance)
    assert np.array_equal(default.mean, one.mean)
    ingest = default.fit_report_.extra["ingest"]
    assert ingest["chips"] == 1
    assert ingest["per_chip"][0]["device"] == str(jax.local_devices()[0])


def test_the_chips_start_at_device_id():
    chunks = _chunks(98, ROWS["few3"])
    model = PCA().setK(K).set("batchRows", BATCH).set("deviceId", 2).set(
        "numDevices", 3).fit(_dataset("callable", chunks))
    local = jax.local_devices()
    assert [c["device"] for c in model.fit_report_.extra["ingest"][
        "per_chip"]] == [str(d) for d in local[2:5]]


def test_more_chips_than_the_process_has_is_refused():
    too_many = len(jax.local_devices()) + 1
    with pytest.raises(ValueError, match="numDevices"):
        PCA().setK(K).set("batchRows", BATCH).set(
            "numDevices", too_many).fit(
            _dataset("callable", _chunks(98, ROWS["few3"])))
    with pytest.raises((ValueError, TypeError)):
        PCA().set("numDevices", 0)


@pytest.mark.parametrize("shift", ["refused", "accepted"])
def test_the_mesh_entry_point_reports_every_row_once(shift):
    chunks = _chunks(98, ROWS["tail7"], shift=shift)
    x = np.concatenate(chunks)
    result = distributed_streaming_pca_fit(
        BatchSource(x, batch_rows=BATCH), K, data_mesh(4))
    rows = result.fit_report_.extra["rows_per_device"]
    assert len(rows) == 4 and sum(rows.values()) == x.shape[0]
    # seven batches in turn: the tail (40 rows) is chip 2's second batch
    assert list(rows.values()) == [128, 128, 64 + 40, 64]
    ingest = result.fit_report_.extra["ingest"]
    assert ingest["chips"] == 4
    assert verdict(ingest)[0] == (shift == "accepted")
    # the means, the Grams of pass 1 — and pass 2's where those were refused
    assert result.fit_report_.collectives["all_reduce"]["count"] == (
        3 if shift == "refused" else 2)


# -- the Pallas path's name, per chip -----------------------------------------


def test_tile_aligned_batches_take_the_pallas_path_on_every_chip(monkeypatch):
    """At a 4096-like width (an even number of feature tiles, whole row
    blocks) each chip's Gram steps go the Pallas way and its masked tail
    the XLA way. The CPU cannot run the kernel, so the platform seam says
    "tpu" and the fused step is stood in for by the XLA one: what is under
    test is the choice, per chip, and its name."""
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_N, _BLOCK_R as br

    n = 2 * _BLOCK_N
    monkeypatch.setattr(streaming, "_gram_platform", lambda acc: "tpu")
    monkeypatch.setattr(
        streaming, "_update_centered_gram_fused_blocked",
        lambda acc, batch, mean, precision=None: streaming.update_centered_gram(
            acc, batch, mean, None, precision=precision))
    rng = np.random.default_rng(5)
    chunks = [(rng.normal(size=(r, n)) + 1.0).astype(np.float32)
              for r in (3 * br, 2 * br + 40)]  # five whole batches and a tail
    ingest = streaming.IngestTrace(device=tuple(jax.local_devices()[:4]))
    cov, mean, count = streaming.stream_covariance(
        BatchSource(lambda: list(chunks), batch_rows=br), dtype=jnp.float32,
        ingest=ingest)
    assert ingest.counters["accumulate_calls"] == {
        "mean": 6, "pallas": 5, "xla": 1}
    _close((np.asarray(cov), np.asarray(mean), int(count)), _plain(chunks))


# -- name guards ----------------------------------------------------------------


def test_the_benchmarks_collective_names_are_what_the_program_emits():
    collective = _bench_module("work/collective.py")
    assert collective.SPANS == streaming.SPAN_COLLECTIVE
    assert collective.PHASE == streaming.PHASE_COLLECTIVE
    assert not set(collective.SPANS.values()) & set(streaming.STREAM_SPANS)
    from spark_rapids_ml_tpu.obs.xprof import TrackedJit

    programs = {name for name, value in vars(pm).items()
                if isinstance(value, TrackedJit)}
    assert programs == set(collective.PROGRAMS)
    # found by substring on ``jit_<name>``, and not by the accumulate
    # family's reader (``work/gram.py``)
    gram = _bench_module("work/gram.py")
    for name in collective.PROGRAMS:
        traced = "jit_" + getattr(pm, name).__name__
        assert name in traced
        assert not any(p in traced for p in gram.PROGRAMS)


@pytest.mark.parametrize("n", [98, 4096])
def test_collective_bytes_are_what_the_program_reckons(n):
    collective = _bench_module("work/collective.py")
    assert collective.mean_bytes(n) == pm.collective_nbytes((n + 1,),
                                                            np.float32)
    assert collective.gram_bytes(n) == pm.collective_nbytes((n, n),
                                                            np.float32)
