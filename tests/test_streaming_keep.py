"""Pass 1's device batches kept for pass 2 of the two-pass streamed fit (CPU).

The CPU reports no ``memory_stats()``, so the budget is 0 here and nothing
is kept; the tests stand in for the chip through the seam
``streaming.keep_budget_bytes`` (the way ``_gram_platform`` is one for the
kernel choice). Every case runs at a tile-aligned width (the 4096 cells'
kind) and at one that is not (784's kind), with whole batches and with a
masked tail. What must hold whatever is kept: the same arrays go through
the same accumulate calls in the same order, so covariance, mean and count
are bit-equal to the loop that keeps nothing.

Since the two-pass fit sums its Gram in pass 1 and runs pass 2 only where
the rows refuse the shift (``tests/test_streaming_shift.py``), every case
runs on both sides of that verdict: ``refused`` is the rows these tests
always had (i.i.d. batches of 64 rows with |mean| >> sigma refuse by their
nature: pass 2 runs, over the kept batches), ``accepted`` the same rows as
mirrored pairs (one walk, and the kept batches let go at the verdict).
"""

from __future__ import annotations

import numpy as np
import pytest
from shift_rows import mirrored_pairs, verdict

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.models import pca as pca_module
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.ops import streaming

BATCH, K = 64, 3
WIDTHS = {"aligned256": 256, "unaligned98": 98}
ROWS = {"whole": (128, 128), "tail": (128, 104)}  # the tail: 40 of 64 rows
SHAPES = [pytest.param(n, rows, id=f"{w}-{r}")
          for w, n in WIDTHS.items() for r, rows in ROWS.items()]
SHIFTS = ("refused", "accepted")
EVERYTHING = 1 << 40


def _chunks(n: int, rows: tuple, shift: str = "refused",
            seed: int = 11) -> list:
    rng = np.random.default_rng(seed)
    # |mean| >> sigma in some columns: the case the two passes exist for
    centre = 50.0 * (np.arange(n) % 3)
    chunks = [(rng.normal(size=(r, n)) + centre).astype(np.float32)
              for r in rows]
    return mirrored_pairs(chunks, centre) if shift == "accepted" else chunks


def _passes(shift: str) -> int:
    """Walks of the rows: pass 2 runs only where the shift is refused."""
    return 2 if shift == "refused" else 1


def _assert_verdict(ingest, shift: str) -> None:
    assert verdict(ingest) == (shift == "accepted", _passes(shift))


def _batch_nbytes(n: int) -> int:
    return BATCH * n * 4


def _batches_per_pass(rows: tuple) -> int:
    return -(-sum(rows) // BATCH)


class _Factory:
    """A zero-argument callable over ``chunks`` that counts its calls."""

    def __init__(self, chunks: list):
        self.chunks = chunks
        self.calls = 0

    def __call__(self):
        self.calls += 1
        return list(self.chunks)


def _budget(monkeypatch, nbytes: int) -> list:
    """Stand in for the chip: the budget is ``nbytes`` whatever the device
    says. Returns the arguments the rule was asked with."""
    asked = []

    def rule(device, batch_nbytes, gram_nbytes):
        asked.append((batch_nbytes, gram_nbytes))
        return nbytes

    monkeypatch.setattr(streaming, "keep_budget_bytes", rule)
    return asked


def _stream(factory, **kwargs):
    source = BatchSource(factory, batch_rows=BATCH)
    ingest = streaming.IngestTrace()
    cov, mean, count = streaming.stream_covariance(source, ingest=ingest,
                                                   **kwargs)
    return (np.asarray(cov), np.asarray(mean), int(count)), ingest


def _assert_same(got, want):
    assert np.array_equal(got[0], want[0])  # covariance, bit for bit
    assert np.array_equal(got[1], want[1])  # mean
    assert got[2] == want[2]


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_everything_kept_crosses_once(monkeypatch, n, rows, shift):
    chunks = _chunks(n, rows, shift)
    today, _ = _stream(_Factory(chunks))
    asked = _budget(monkeypatch, EVERYTHING)
    factory = _Factory(chunks)
    kept, ingest = _stream(factory)
    _assert_same(kept, today)
    assert kept[2] == sum(rows)
    per_pass = _batches_per_pass(rows)
    c = ingest.counters
    assert asked == [(_batch_nbytes(n), n * n * 4)]
    assert c["keep_budget_bytes"] == EVERYTHING
    assert c["batches_kept"] == per_pass
    assert c["bytes_kept"] == per_pass * _batch_nbytes(n)
    assert c["batches"] == per_pass  # only what really crossed
    assert c["rows_put"] == per_pass * BATCH
    assert c["bytes_put"] == per_pass * _batch_nbytes(n)  # one crossing
    # refused: two walks of the rows, one of them on the chip
    _assert_verdict(ingest, shift)
    assert c["accumulate_calls"] == {"mean": per_pass, "pallas": 0,
                                     "xla": _passes(shift) * per_pass}
    # BatchSource called it once to probe and once to peek; pass 1 walked
    # it once; pass 2 did not walk it at all
    assert factory.calls == 3


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_a_kept_prefix_is_passed_over_and_the_rest_put_again(
        monkeypatch, n, rows, shift):
    chunks = _chunks(n, rows, shift)
    today, _ = _stream(_Factory(chunks))
    _budget(monkeypatch, _batch_nbytes(n) * 3 // 2)
    put_first_values = []
    put = streaming.IngestTrace.put

    def recording_put(self, batch, mask, dtype):
        # the one place the loop touches a host batch's rows
        put_first_values.append(float(batch[0, 0]))
        return put(self, batch, mask, dtype)

    monkeypatch.setattr(streaming.IngestTrace, "put", recording_put)
    factory = _Factory(chunks)
    kept, ingest = _stream(factory)
    _assert_same(kept, today)
    per_pass = _batches_per_pass(rows)
    c = ingest.counters
    _assert_verdict(ingest, shift)
    refused = shift == "refused"
    assert c["batches_kept"] == 1 and c["bytes_kept"] == _batch_nbytes(n)
    puts = 2 * per_pass - 1 if refused else per_pass
    assert c["batches"] == puts
    assert c["bytes_put"] == puts * _batch_nbytes(n)
    assert c["rows_put"] == puts * BATCH
    assert c["accumulate_calls"]["xla"] == _passes(shift) * per_pass
    firsts = [float(row[0]) for row in np.concatenate(chunks)[::BATCH]]
    assert len(firsts) == per_pass
    # pass 1 put every batch, pass 2 every batch but the kept first one;
    # an accepted shift has no pass 2, and the tail does not cross again
    assert put_first_values == firsts + (firsts[1:] if refused else [])
    # probe, peek, pass 1, pass 2
    assert factory.calls == (4 if refused else 3)


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_without_memory_stats_nothing_is_kept(n, rows, shift):
    """The CPU as it is: today's loop and today's counters, where the
    shift is refused; one crossing all the same where it is accepted."""
    factory = _Factory(_chunks(n, rows, shift))
    _, ingest = _stream(factory)
    _assert_verdict(ingest, shift)
    per_pass = _batches_per_pass(rows)
    c = ingest.counters
    assert (c["batches_kept"], c["bytes_kept"], c["keep_budget_bytes"]) == (
        0, 0, 0)
    assert c["batches"] == _passes(shift) * per_pass
    assert c["bytes_put"] == _passes(shift) * per_pass * _batch_nbytes(n)
    assert c["rows_put"] == _passes(shift) * per_pass * BATCH
    assert factory.calls == 2 + _passes(shift)


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_a_stale_factory_still_raises_on_a_prefix_kept_walk(
        monkeypatch, n, rows, shift):
    chunks = _chunks(n, rows, shift)
    state = {"fresh": True}

    def factory():
        # fresh for BatchSource's probe and peek and for pass 1, then an
        # iterator somebody has already taken the first chunk from
        return iter(chunks if state["fresh"] else chunks[1:])

    source = BatchSource(factory, batch_rows=BATCH)
    assert source.reiterable
    batches = source.batches

    def batches_then_stale():
        yield from batches()
        state["fresh"] = False

    monkeypatch.setattr(source, "batches", batches_then_stale)
    _budget(monkeypatch, _batch_nbytes(n) * 3 // 2)
    ingest = streaming.IngestTrace()
    if shift == "refused":
        with pytest.raises(RuntimeError, match="FRESH iterator"):
            streaming.stream_covariance(source, ingest=ingest)
    else:  # the source is not walked again: nothing stale to be handed
        _, _, count = streaming.stream_covariance(source, ingest=ingest)
        assert int(count) == sum(rows)
    assert ingest.counters["batches_kept"] == 1
    assert not ingest.kept


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_no_kept_batch_outlives_the_walk(monkeypatch, n, rows, shift):
    chunks = _chunks(n, rows, shift)
    _budget(monkeypatch, EVERYTHING)
    _, ingest = _stream(_Factory(chunks))
    _assert_verdict(ingest, shift)
    per_pass = _batches_per_pass(rows)
    assert ingest.counters["batches_kept"] == per_pass
    assert not ingest.kept  # empty at return: let go at the verdict

    step = streaming.update_centered_gram_auto
    seen = []
    # the step that dies: pass 2's second (refused), pass 1's second
    dies_at = per_pass + 2 if shift == "refused" else 2

    def a_step_fails(gram_acc, x_dev, mean, m_dev=None, precision=None):
        seen.append(len(ingest.kept))
        if len(seen) == dies_at:
            raise FloatingPointError("a Gram step dies")
        return step(gram_acc, x_dev, mean, m_dev, precision=precision)

    monkeypatch.setattr(streaming, "update_centered_gram_auto", a_step_fails)
    ingest = streaming.IngestTrace()
    with pytest.raises(FloatingPointError):
        streaming.stream_covariance(
            BatchSource(_Factory(chunks), batch_rows=BATCH), ingest=ingest)
    # pass 1 keeps a batch once its steps are out; in pass 2 each reference
    # left the list as its batch was handed to its step
    pass_1 = list(range(per_pass))
    assert seen == (pass_1 + [per_pass - 1, per_pass - 2]
                    if shift == "refused" else pass_1[:2])
    assert not ingest.kept  # and the rest went with the exception


@pytest.mark.parametrize("n,rows", SHAPES)
def test_one_pass_keeps_nothing(monkeypatch, n, rows):
    asked = _budget(monkeypatch, EVERYTHING)
    chunks = _chunks(n, rows)
    for source, kwargs in [(iter(chunks), {}),  # a one-shot iterator
                           (_Factory(chunks), {"mean_centering": False})]:
        _, ingest = _stream(source, **kwargs)
        c = ingest.counters
        assert c["passes"] == 1
        assert (c["batches_kept"], c["bytes_kept"],
                c["keep_budget_bytes"]) == (0, 0, 0)
        assert c["bytes_put"] == _batches_per_pass(rows) * _batch_nbytes(n)
    assert asked == []  # the branch never asks


@pytest.mark.parametrize("shift", SHIFTS)
@pytest.mark.parametrize("n,rows", SHAPES)
def test_fit_reports_the_counters_and_emits_no_new_span(monkeypatch, n, rows,
                                                        shift):
    chunks = _chunks(n, rows, shift)

    def fit():
        return PCA().setK(K).set("batchRows", BATCH).set(
            "dtype", "float32").fit(_Factory(chunks))

    today = fit()
    _budget(monkeypatch, EVERYTHING)
    model = fit()
    assert np.array_equal(model.pc, today.pc)
    assert np.array_equal(model.explained_variance, today.explained_variance)
    assert np.array_equal(model.mean, today.mean)
    per_pass = _batches_per_pass(rows)
    ingest = model.fit_report_.extra["ingest"]
    _assert_verdict(ingest, shift)
    assert ingest["batches_kept"] == per_pass
    assert ingest["bytes_put"] == ingest["bytes_kept"] == (
        per_pass * _batch_nbytes(n))
    # with nothing kept a refused shift crosses the rows a second time
    assert today.fit_report_.extra["ingest"]["bytes_put"] == (
        _passes(shift) * ingest["bytes_put"])
    # the dataset is sized as before: rows without padding, bytes once
    assert model.fit_report_.rows == sum(rows)
    assert model.fit_report_.bytes_processed == sum(rows) * n * 4
    events = sorted(obs_spans.get_recorder().events(model.fit_report_.trace_id),
                    key=lambda e: (e.ts_us, -e.dur_us))
    # pass 1 dispatches each batch's Gram step behind its mean step; the
    # one blocking read (``stream:sync/count``) is the verdict's, and a
    # refused shift's pass 2 has no host stage left: its steps only (the
    # source's own stages inside ``stream:next`` are
    # tests/test_arrow_ingest.py's)
    # nor are the landing watchers', on lines of their own: one a put
    # (``tests/test_streaming_landing.py``)
    inside_next = streaming.SPAN_NEXT_PART.values()
    landed = [e for e in events if e.name.startswith(streaming.SPAN_LANDING)]
    assert len(landed) == per_pass
    pass_2 = [streaming.SPAN_PASS_GRAM] + [
        streaming.SPAN_ACCUMULATE["xla"]] * per_pass
    assert [e.name for e in events
            if e.name not in inside_next and e not in landed] == (
        [pca_module.SPAN_FIT, pca_module.SPAN_STREAMED_COV,
         streaming.SPAN_PASS_MEAN]
        + [streaming.SPAN_NEXT, streaming.SPAN_PUT,
           streaming.SPAN_ACCUMULATE["mean"],
           streaming.SPAN_ACCUMULATE["xla"]] * per_pass
        + [streaming.SPAN_NEXT, streaming.SPAN_SYNC_COUNT]
        + (pass_2 if shift == "refused" else [])
        + [streaming.SPAN_SYNC_COV,
           pca_module.SPAN_XLA_EIGH, pca_module.SPAN_FETCH])


# -- the rule itself ----------------------------------------------------------


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


GIB = 1 << 30


@pytest.mark.parametrize("stats,batch,gram,want", [
    # a 16 GB chip with little else on it: both 2pass cells keep all four
    pytest.param({"bytes_limit": 15 * GIB + 768 * (1 << 20),
                  "bytes_in_use": 96 << 20}, 2 * GIB, 64 << 20,
                 15 * GIB + 768 * (1 << 20) - (96 << 20) - 6 * GIB
                 - (192 << 20), id="room"),
    # less free than the loop needs without keeping: nothing, not a negative
    pytest.param({"bytes_limit": 16 * GIB, "bytes_in_use": 11 * GIB},
                 2 * GIB, 64 << 20, 0, id="no-room"),
    pytest.param({"bytes_limit": 16 * GIB, "bytes_in_use": 16 * GIB},
                 GIB, GIB, 0, id="full"),
    # a backend that reports no memory, or not the two numbers needed
    pytest.param(None, GIB, GIB, 0, id="no-stats"),
    pytest.param({}, GIB, GIB, 0, id="empty-stats"),
    pytest.param({"bytes_in_use": GIB, "peak_bytes_in_use": 2 * GIB},
                 GIB, GIB, 0, id="no-limit"),
])
def test_the_budget_is_what_is_free_less_what_the_loop_needs(
        stats, batch, gram, want):
    assert streaming.keep_budget_bytes(_Device(stats), batch, gram) == want


def test_the_budget_leaves_three_batches_and_three_grams():
    device = _Device({"bytes_limit": 1000, "bytes_in_use": 100})
    assert streaming.keep_budget_bytes(device, 100, 10) == 900 - 300 - 30
    # and what is kept never passes it: 5 batches of 100 fit in 570
    ingest = streaming.IngestTrace()
    ingest.chips[0].keep_room = streaming.keep_budget_bytes(device, 100, 10)
    batch = np.zeros((25,), dtype=np.float32)  # 100 bytes
    for _ in range(8):
        ingest.put_rows = 25
        ingest.keep(0, batch, None)
    assert len(ingest.kept) == 5
    assert sum(rows for _, _, rows, _, _ in ingest.kept) == 125
    assert ingest.counters["bytes_kept"] == 500


def test_the_real_cpu_device_gives_no_budget():
    import jax

    assert streaming.keep_budget_bytes(jax.local_devices()[0], 1, 1) == 0
