"""The two-pass fit's Gram steps under the crossing (CPU).

Pass 1 of a two-pass streamed fit also runs each batch's centred-Gram step,
about the mean of the chip's first batch, and ``recentre_gram`` moves the
sum to the mean of all rows once that is known; the same program reads from
the rows how much the shift cost (ρ) and the fit runs pass 2 over the kept
batches, as it always did, when ρ passes ``SHIFT_RATIO_MAX``. What must
hold: an accepted shift gives the two-pass covariance to float32 rounding
(against a float64 oracle, as close as pass 2 comes), a refused one gives
pass 2's very bits and says so in the counters, and the Gram step of batch
*i* is dispatched before the loop waits for the window of put *i* + 2.

The parent's result is read by holding the threshold below any ρ
(``SHIFT_RATIO_MAX`` = −1: every fit falls back to pass 2, unchanged).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shift_rows import verdict

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.ops import streaming

EVERYTHING = 1 << 40


def _stream(dataset, batch_rows: int, chips=None):
    device = None if chips is None else tuple(jax.local_devices()[:chips])
    ingest = streaming.IngestTrace(device=device)
    cov, mean, count = streaming.stream_covariance(
        BatchSource(dataset, batch_rows=batch_rows), dtype=jnp.float32,
        ingest=ingest)
    return (np.asarray(cov), np.asarray(mean), int(count)), ingest


def _parent(monkeypatch, dataset, batch_rows: int, chips=None):
    """Pass 2 whatever the rows say: what the fit returned before it had a
    shift."""
    with monkeypatch.context() as m:
        m.setattr(streaming, "SHIFT_RATIO_MAX", -1.0)
        got, ingest = _stream(dataset, batch_rows, chips)
    assert verdict(ingest) == (False, 2)
    return got


def _cov_error(got, x: np.ndarray) -> float:
    """Largest error of the covariance against the float64 two-pass oracle,
    as a share of its largest entry."""
    x = x.astype(np.float64)
    centred = x - x.mean(axis=0)
    cov = centred.T @ centred / (x.shape[0] - 1)
    return float(np.max(np.abs(got[0] - cov)) / np.max(np.abs(cov)))


def _iid(rows: int, n: int, mean: float = 0.0, seed: int = 3) -> np.ndarray:
    rng = np.random.default_rng(seed)
    scale = np.linspace(2.0, 0.5, n)
    return (rng.normal(size=(rows, n)) * scale + mean).astype(np.float32)


# -- accepted: i.i.d. rows --------------------------------------------------------

# (rows, batch rows, features, chips): whole batches on the XLA path, a
# masked ragged tail, and two and four forced host devices
ACCEPTED = [pytest.param(16384, 4096, 98, None, id="xla-whole"),
            pytest.param(16384 - 1000, 4096, 98, None, id="ragged-tail"),
            pytest.param(32768, 4096, 64, 2, id="2chips"),
            pytest.param(32768 + 1000, 4096, 64, 4, id="4chips-tail")]


@pytest.mark.parametrize("rows,batch,n,chips", ACCEPTED)
def test_an_accepted_shift_is_the_two_pass_result_to_rounding(
        monkeypatch, rows, batch, n, chips):
    x = _iid(rows, n, mean=0.5)
    got, ingest = _stream(lambda: [x], batch, chips)
    accepted, passes = verdict(ingest)
    assert accepted and passes == 1
    c = ingest.counters
    assert 0 < c["gram_shift"]["ratio"] <= streaming.SHIFT_RATIO_MAX
    per_pass = -(-rows // batch)
    assert c["batches"] == per_pass  # the rows crossed once
    assert c["accumulate_calls"] == {"mean": per_pass, "pallas": 0,
                                     "xla": per_pass}
    parent = _parent(monkeypatch, lambda: [x], batch, chips)
    assert got[2] == parent[2] == rows
    # the mean is the parent's program's: the same bits
    assert np.array_equal(got[1], parent[1])
    # float32 rounding against the float64 oracle, and as close as pass 2
    err, parent_err = _cov_error(got, x), _cov_error(parent, x)
    assert err <= 1e-6
    assert err <= 2 * parent_err + 1e-7
    assert np.max(np.abs(got[0] - parent[0])) <= 1e-6 * np.max(np.abs(
        parent[0]))
    # each chip has a verdict of its own; the fit's ratio is the largest
    chip_ratios = [chip["gram_shift"]["ratio"] for chip in c["per_chip"]]
    assert all(chip["gram_shift"]["accepted"] for chip in c["per_chip"])
    assert c["gram_shift"]["ratio"] == max(chip_ratios)


def test_a_far_mean_is_accepted_and_no_worse_than_pass_2(monkeypatch):
    """|μ| ≫ σ, the case the two passes exist for: N(10, 1)-like rows in
    batches large enough that the float32 mean's rounding leaks less
    through the correction than the Gram's own rounding."""
    x = _iid(65536, 16, mean=10.0)
    got, ingest = _stream(lambda: [x], 16384)
    assert verdict(ingest) == (True, 1)
    parent = _parent(monkeypatch, lambda: [x], 16384)
    assert _cov_error(got, x) <= 2 * _cov_error(parent, x) + 1e-7
    # the raw-moment form would not be: Σxxᵀ − n μμᵀ loses |μ|²/σ² digits
    assert _cov_error(got, x) <= 1e-6


def test_a_far_mean_in_small_batches_is_refused(monkeypatch):
    """N(100, 1) in batches of 2,048 rows: the first batch's mean is off by
    σ/45, and the mean's own float32 rounding (a few ulp of 100) through
    the correction would cost a digit — ρ's second term says so and the
    fit is pass 2's, bit for bit."""
    x = _iid(8192, 16, mean=100.0)
    got, ingest = _stream(lambda: [x], 2048)
    assert verdict(ingest) == (False, 2)
    assert ingest.counters["gram_shift"]["ratio"] > streaming.SHIFT_RATIO_MAX
    parent = _parent(monkeypatch, lambda: [x], 2048)
    assert np.array_equal(got[0], parent[0])
    assert np.array_equal(got[1], parent[1])


# -- refused: the first batch is not like the rest --------------------------------


@pytest.mark.parametrize("chips", [None, 2, 4])
def test_rows_sorted_by_a_feature_are_refused_and_bit_equal(monkeypatch,
                                                            chips):
    x = _iid(16384, 98, mean=0.5)
    x = x[np.argsort(x[:, 0])]  # a frame ordered by its first column
    got, ingest = _stream(lambda: [x], 2048, chips)
    accepted, passes = verdict(ingest)
    assert not accepted and passes == 2
    c = ingest.counters
    assert c["gram_shift"]["ratio"] > 0.3  # column 0: nearly all of it
    assert not c["per_chip"][0]["gram_shift"]["accepted"]
    per_pass = 16384 // 2048
    # as dispatched: pass 1's shifted steps and pass 2's
    assert c["accumulate_calls"] == {"mean": per_pass, "pallas": 0,
                                     "xla": 2 * per_pass}
    assert c["batches"] == 2 * per_pass  # nothing kept on the CPU
    parent = _parent(monkeypatch, lambda: [x], 2048, chips)
    assert np.array_equal(got[0], parent[0])
    assert np.array_equal(got[1], parent[1])
    assert got[2] == parent[2]
    assert _cov_error(got, x) <= 1e-6


def test_a_drifting_stream_is_refused():
    rng = np.random.default_rng(9)
    chunks = [(rng.normal(size=(2048, 32)) + 0.5 * i).astype(np.float32)
              for i in range(6)]
    _, ingest = _stream(lambda: list(chunks), 2048)
    assert verdict(ingest) == (False, 2)


def test_a_constant_column_reads_zero():
    x = _iid(16384, 16)
    x[:, 3] = 2.5  # constant at the shift: 0/0, not a NaN
    x[:, 7] = 0.0  # a pixel nobody lit
    got, ingest = _stream(lambda: [x], 8192)
    assert verdict(ingest) == (True, 1)
    # the other columns' ρ, finite: the constant ones added a 0 to the max
    assert 0 < ingest.counters["gram_shift"]["ratio"] < 1.0 / 64
    assert np.all(got[0][3] == 0) and np.all(got[0][:, 7] == 0)
    assert np.all(np.isfinite(got[0]))
    only = np.full((4096, 4), 2.5, np.float32)
    _, ingest = _stream(lambda: [only], 1024)
    assert ingest.counters["gram_shift"] == {"accepted": True, "ratio": 0.0}


def test_one_batch_is_its_own_mean():
    """A fit of one batch: the shift IS the mean of all rows, ρ = 0, and
    the rows cross once on any backend."""
    x = _iid(512, 24, mean=30.0)
    got, ingest = _stream(lambda: [x], 512)
    assert verdict(ingest) == (True, 1)
    assert ingest.counters["gram_shift"]["ratio"] == 0.0
    assert ingest.counters["batches"] == 1
    assert _cov_error(got, x) <= 1e-6


def test_a_nan_refuses():
    x = _iid(4096, 8)
    x[100, 2] = np.nan
    _, ingest = _stream(lambda: [x], 1024)
    assert verdict(ingest) == (False, 2)


def test_the_threshold_is_a_sixty_fourth():
    assert streaming.SHIFT_RATIO_MAX == 1.0 / 64


# -- the program alone ------------------------------------------------------------


@pytest.mark.parametrize("own_mean", [True, False],
                         ids=["one-chip", "a-chip-of-several"])
def test_recentre_gram_is_the_identity_it_says(own_mean):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(300, 12)) + 3.0
    c = x[:100].mean(axis=0)
    mu = x.mean(axis=0) if own_mean else x.mean(axis=0) + rng.normal(size=12)
    shifted = (x - c).T @ (x - c)
    centred, ratio = streaming.recentre_gram(
        jnp.asarray(shifted), jnp.asarray(x.sum(axis=0)),
        jnp.asarray(300, jnp.int32), jnp.asarray(c), jnp.asarray(mu))
    np.testing.assert_allclose(np.asarray(centred), (x - mu).T @ (x - mu),
                               rtol=1e-9, atol=1e-9)
    d = mu - c
    want = np.max(300 * np.abs(d) * (np.abs(d) + np.abs(mu) / 32)
                  / np.diag(shifted))
    assert float(ratio) == pytest.approx(want, rel=1e-9)


def test_recentre_gram_is_tracked_and_no_accumulate_program():
    """Found in a device trace by its own name, and not by the accumulate
    family's reader (``benchmarks/work/gram.py`` ``PROGRAMS``: the Gram
    steps it moves under the crossing are the programs they were)."""
    from test_streaming_spans import _bench_module

    from spark_rapids_ml_tpu.obs.xprof import TrackedJit

    assert isinstance(streaming.recentre_gram, TrackedJit)
    traced = "jit_" + streaming.recentre_gram.__name__
    assert not any(p in traced for p in _bench_module("work/gram.py").PROGRAMS)


# -- beyond the budget, stale factories, the order of dispatch --------------------


class _Factory:
    def __init__(self, chunks: list):
        self.chunks, self.calls = chunks, 0

    def __call__(self):
        self.calls += 1
        return list(self.chunks)


def _budget(monkeypatch, nbytes: int) -> None:
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda device, reserve_nbytes: nbytes)


@pytest.mark.parametrize("refused", [True, False], ids=["refused", "accepted"])
def test_a_source_beyond_the_budget_puts_its_tail_again_only_if_refused(
        monkeypatch, refused):
    batch, n = 2048, 32
    x = _iid(4 * batch, n, mean=0.5)
    if refused:
        x = x[np.argsort(x[:, 0])]
    _budget(monkeypatch, 2 * batch * n * 4 + 1)  # two of four batches kept
    factory = _Factory([x])
    got, ingest = _stream(factory, batch)
    c = ingest.counters
    assert verdict(ingest) == (not refused, 2 if refused else 1)
    assert c["batches_kept"] == 2
    # refused: the kept prefix is replayed and the tail crosses again
    assert c["batches"] == (4 + 2 if refused else 4)
    assert c["bytes_put"] == c["batches"] * batch * n * 4
    # probe, peek, pass 1 — and pass 2's walk of the tail
    assert factory.calls == (4 if refused else 3)
    assert not ingest.kept  # let go at the verdict, or as handed out
    assert _cov_error(got, x) <= 1e-6


@pytest.mark.parametrize("refused", [True, False], ids=["refused", "accepted"])
def test_a_stale_factory_raises_where_the_source_is_walked_again(
        monkeypatch, refused):
    batch, n = 2048, 32
    x = _iid(4 * batch, n, mean=0.5)
    if refused:
        x = x[np.argsort(x[:, 0])]
    chunks = [x[:2 * batch], x[2 * batch:]]
    state = {"fresh": True}
    source = BatchSource(
        lambda: iter(chunks if state["fresh"] else chunks[1:]),
        batch_rows=batch)
    batches = source.batches

    def batches_then_stale():
        yield from batches()
        state["fresh"] = False

    monkeypatch.setattr(source, "batches", batches_then_stale)
    _budget(monkeypatch, batch * n * 4 + 1)
    ingest = streaming.IngestTrace()
    if refused:
        with pytest.raises(RuntimeError, match="FRESH iterator"):
            streaming.stream_covariance(source, ingest=ingest)
    else:  # one walk: nothing to compare it with, and nothing went wrong
        _, _, count = streaming.stream_covariance(source, ingest=ingest)
        assert int(count) == 4 * batch
    assert not ingest.kept


@pytest.mark.parametrize("chips", [None, 2])
def test_a_batchs_gram_step_is_dispatched_before_the_wait_for_put_i_plus_2(
        monkeypatch, chips):
    """On the chip the step of batch *i* then starts at landing *i* + 1,
    under crossing *i* + 2, as the one-pass fit's fused step does: the
    loop must not stand in the window's wait with that step undispatched."""
    events = []
    put, step = streaming.IngestTrace.put, streaming.update_centered_gram_auto

    def recording_put(ingest, batch, mask, dtype):
        c, x_dev, m_dev = put(ingest, batch, mask, dtype)
        events.append(("put", c, id(x_dev)))
        return c, x_dev, m_dev

    def recording_step(gram_acc, x_dev, mean, m_dev=None, precision=None):
        events.append(("gram", id(x_dev)))
        return step(gram_acc, x_dev, mean, m_dev, precision=precision)

    monkeypatch.setattr(streaming.IngestTrace, "put", recording_put)
    monkeypatch.setattr(streaming, "wait_for_landing",
                        lambda x_dev: events.append(("wait", id(x_dev))))
    monkeypatch.setattr(streaming, "update_centered_gram_auto",
                        recording_step)
    _budget(monkeypatch, EVERYTHING)
    x = _iid(12 * 2048, 16, mean=0.5)
    _, ingest = _stream(lambda: [x], 2048, chips)
    assert verdict(ingest) == (True, 1)
    puts = [e[2] for e in events if e[0] == "put"]
    assert len(puts) == 12
    for batch in puts:
        # put i, then its Gram step, before anything is done for put i+1
        at = events.index(next(e for e in events
                               if e[0] == "put" and e[2] == batch))
        assert events[at + 1] == ("gram", batch)
    # and every wait is for a batch whose Gram step is already out
    stepped = set()
    for event in events:
        if event[0] == "gram":
            stepped.add(event[1])
        elif event[0] == "wait":
            assert event[1] in stepped
    assert sum(e[0] == "wait" for e in events) == 12 - 2 * (chips or 1)


# -- the estimator's door ---------------------------------------------------------


def test_fit_reports_the_verdict_per_chip():
    x = _iid(16384, 32, mean=0.5)
    model = PCA().setK(3).set("batchRows", 4096).set("dtype", "float32").set(
        "numDevices", 2).fit(lambda: [x])
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["passes"] == 1
    assert ingest["gram_shift"]["accepted"] is True
    assert [chip["gram_shift"]["accepted"] for chip in ingest["per_chip"]] \
        == [True, True]
    assert model.fit_report_.as_dict()["extra"]["ingest"]["gram_shift"] == \
        ingest["gram_shift"]
    # two all-reduces, as before: the means, then the re-centred Grams
    assert model.fit_report_.collectives["all_reduce"]["count"] == 2
    one_pass = PCA().setK(3).set("batchRows", 4096).fit(iter([x]))
    assert "gram_shift" not in one_pass.fit_report_.extra["ingest"]
