"""At most two puts in flight on a chip (CPU).

On the chip a put's third-in-flight waits for the chip's oldest put to land
(``streaming.wait_for_landing``: ``block_until_ready`` on the batch array
``device_put`` returned). The CPU lands a put at once, so the tests stand in
for the chip through that one function, the way ``test_streaming_keep.py``
patches ``keep_budget_bytes``: they record which array each wait was for and
where it fell among the puts. What must hold: the wait before a chip's put
*i* is for that chip's put *i* − 2 and no other, the counters say so, and
the arrays that reach the accumulate programs are the ones they were.
"""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest
from shift_rows import mirrored_pairs, verdict

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.parallel import data_mesh
from spark_rapids_ml_tpu.parallel.streaming import DistributedStreamingPCA

N, BATCH, K = 24, 32, 3
EVERYTHING = 1 << 40
# how the rows are handed over → (input form, keep budget, passes that put)
# (i.i.d. batches of 32 rows refuse the two-pass fit's shifted Gram, so pass
# 2 runs; the ``_accepted`` form is the same rows as mirrored pairs: one walk)
FORMS = {
    "one_pass": ("iterator", 0, 1),
    "two_pass": ("callable", 0, 2),  # the CPU as it is: both passes put
    "two_pass_kept": ("callable", EVERYTHING, 1),  # the chip: pass 1 only
    "two_pass_accepted": ("callable", 0, 1),  # no pass 2 to put anything
}
BATCHES = (1, 2, 3, 6)


def _chunks(batches: int, seed: int = 5, form: str = "") -> list:
    rng = np.random.default_rng(seed)
    # two chunks, so a batch may straddle them; whole batches only
    rows = BATCH * batches
    centre = 4.0 * (np.arange(N) % 3)
    x = (rng.normal(size=(rows, N)) + centre).astype(np.float32)
    if form.endswith("_accepted"):
        (x,) = mirrored_pairs([x], centre)
    return [x] if batches == 1 else [x[: rows // 2], x[rows // 2:]]


def _dataset(input_form: str, chunks: list):
    return (lambda: list(chunks)) if input_form == "callable" else iter(chunks)


class _Log:
    """Puts and waits in the order they happened, arrays by identity."""

    def __init__(self, monkeypatch, seconds: float = 0.0):
        self.events = []  # ("put", chip, array) | ("wait", array)
        put = streaming.IngestTrace.put

        def recording_put(ingest, batch, mask, dtype):
            c, x_dev, m_dev = put(ingest, batch, mask, dtype)
            self.events.append(("put", c, x_dev))
            return c, x_dev, m_dev

        def wait(x_dev):
            if seconds:
                time.sleep(seconds)
            self.events.append(("wait", x_dev))

        monkeypatch.setattr(streaming.IngestTrace, "put", recording_put)
        monkeypatch.setattr(streaming, "wait_for_landing", wait)

    def puts(self, chip: int = None) -> list:
        return [e[2] for e in self.events if e[0] == "put"
                and chip in (None, e[1])]

    def waits(self) -> list:
        return [e[1] for e in self.events if e[0] == "wait"]

    def assert_each_wait_is_for_the_chips_put_before_last(self, window=2):
        """The wait that precedes a chip's put *i* (it is logged before the
        put it made room for) is for that chip's put *i* − ``window``."""
        seen = {}  # chip → its puts so far
        pending = []
        for event in self.events:
            if event[0] == "wait":
                pending.append(event[1])
                continue
            _, c, x_dev = event
            mine = seen.setdefault(c, [])
            if len(mine) >= window:
                assert len(pending) == 1 and pending[0] is mine[-window]
            else:
                assert pending == []
            pending = []
            mine.append(x_dev)
        assert pending == []  # no wait but before a put


def _budget(monkeypatch, nbytes: int) -> None:
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda device, batch_nbytes, gram_nbytes: nbytes)


def _stream(dataset, chips=None, **kwargs):
    device = None if chips is None else tuple(jax.local_devices()[:chips])
    ingest = streaming.IngestTrace(device=device)
    source = BatchSource(dataset, batch_rows=BATCH)
    cov, mean, count = streaming.stream_covariance(source, ingest=ingest,
                                                   **kwargs)
    return (np.asarray(cov), np.asarray(mean), int(count)), ingest


@pytest.mark.parametrize("batches", BATCHES)
@pytest.mark.parametrize("form", sorted(FORMS))
def test_one_chip_waits_for_the_put_before_last(monkeypatch, form, batches):
    input_form, budget, putting_passes = FORMS[form]
    _budget(monkeypatch, budget)
    log = _Log(monkeypatch)
    got, ingest = _stream(_dataset(input_form, _chunks(batches, form=form)))
    assert got[2] == BATCH * batches
    if input_form == "callable":
        # one batch is its own mean: its shift is accepted whatever the rows
        accepted = form == "two_pass_accepted" or batches == 1
        assert verdict(ingest) == (accepted, 1 if accepted else 2)
        putting_passes = 1 if accepted else putting_passes
    puts = putting_passes * batches
    c = ingest.counters
    assert c["batches"] == puts == len(log.puts())
    assert c["put_waits"] == max(0, puts - 2) == len(log.waits())
    assert c["puts_in_flight_max"] == min(2, puts)
    log.assert_each_wait_is_for_the_chips_put_before_last()
    # FIFO: the waits are for the puts in the order they went out
    assert all(w is p for w, p in zip(log.waits(), log.puts()))
    (chip,) = c["per_chip"]
    for key in ("put_waits", "puts_in_flight_max", "put_wait_seconds"):
        assert chip[key] == c[key]


@pytest.mark.parametrize("form", sorted(FORMS))
def test_four_chips_each_wait_for_their_own_oldest_put(monkeypatch, form):
    input_form, budget, putting_passes = FORMS[form]
    _budget(monkeypatch, budget)
    log = _Log(monkeypatch)
    _, ingest = _stream(_dataset(input_form, _chunks(16, form=form)), chips=4)
    if input_form == "callable":
        assert verdict(ingest)[0] == (form == "two_pass_accepted")
    c = ingest.counters
    per_chip_puts = 4 * putting_passes
    assert c["batches"] == 4 * per_chip_puts
    # the first eight puts of the fit go out with no wait at all
    assert [e[0] for e in log.events[:9]] == ["put"] * 8 + ["wait"]
    log.assert_each_wait_is_for_the_chips_put_before_last()
    for i, chip in enumerate(c["per_chip"]):
        assert len(log.puts(i)) == per_chip_puts  # dealt in turn
        assert chip["put_waits"] == per_chip_puts - 2
        assert chip["puts_in_flight_max"] == 2
    assert c["put_waits"] == sum(ch["put_waits"] for ch in c["per_chip"])
    assert c["put_wait_seconds"] == pytest.approx(
        sum(ch["put_wait_seconds"] for ch in c["per_chip"]))
    assert c["puts_in_flight_max"] == 2  # a chip's, not the fit's sum


@pytest.mark.parametrize("kept", (1, 3))
def test_replayed_puts_count_against_the_same_window(monkeypatch, kept):
    """A source beyond the keep budget: pass 2 hands out the kept prefix and
    puts the rest again, through the same ``put``."""
    batches = 5
    _budget(monkeypatch, kept * BATCH * N * 4 + 1)
    log = _Log(monkeypatch)
    _, ingest = _stream(_dataset("callable", _chunks(batches)))
    c = ingest.counters
    assert c["batches_kept"] == kept
    puts = batches + (batches - kept)
    assert c["batches"] == puts == len(log.puts())
    assert c["put_waits"] == puts - 2
    # pass 2's first put waits for pass 1's put before last: one window
    # for the fit, not one a pass
    log.assert_each_wait_is_for_the_chips_put_before_last()


@pytest.mark.parametrize("window", (1, 3))
def test_the_constant_is_the_window(monkeypatch, window):
    monkeypatch.setattr(streaming, "PUTS_IN_FLIGHT", window)
    log = _Log(monkeypatch)
    _, ingest = _stream(_dataset("iterator", _chunks(6)))
    assert ingest.counters["put_waits"] == 6 - window
    assert ingest.counters["puts_in_flight_max"] == window
    log.assert_each_wait_is_for_the_chips_put_before_last(window)


def test_the_window_is_two_and_the_fence_is_block_until_ready(monkeypatch):
    assert streaming.PUTS_IN_FLIGHT == 2
    blocked = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or x)
    x_dev = jax.device_put(np.zeros((2, 2), np.float32))
    streaming.wait_for_landing(x_dev)
    assert blocked == [x_dev] and blocked[0] is x_dev


@pytest.mark.parametrize("chips", (1, 4))
@pytest.mark.parametrize("input_form", ("callable", "iterator"))
def test_model_is_bit_equal_with_the_wait_a_no_op(monkeypatch, input_form,
                                                  chips):
    chunks = _chunks(12)  # three a chip on four

    def fit():
        return PCA().setK(K).set("batchRows", BATCH).set(
            "dtype", "float32").setNumDevices(chips).fit(
                _dataset(input_form, chunks))

    waited = fit()
    assert waited.fit_report_.extra["ingest"]["put_waits"] > 0
    monkeypatch.setattr(streaming, "wait_for_landing", lambda x_dev: None)
    bare = fit()
    assert np.array_equal(waited.pc, bare.pc)
    assert np.array_equal(waited.explained_variance, bare.explained_variance)
    assert np.array_equal(waited.mean, bare.mean)


@pytest.mark.parametrize("input_form", ("callable", "iterator"))
def test_wait_seconds_are_inside_the_put_phase(monkeypatch, input_form):
    nap = 0.01
    _Log(monkeypatch, seconds=nap)
    model = PCA().setK(K).set("batchRows", BATCH).set("dtype", "float32").fit(
        _dataset(input_form, _chunks(4)))
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["put_waits"] == (6 if input_form == "callable" else 2)
    assert ingest["put_wait_seconds"] >= nap * ingest["put_waits"]
    assert ingest["put_wait_seconds"] <= model.fit_timings_["covariance/put"]
    # the slowest put stage holds a wait
    assert nap <= ingest["put_seconds_max"] <= \
        model.fit_timings_["covariance/put"]


@pytest.mark.parametrize("input_form", ("callable", "iterator"))
def test_no_batch_outlives_the_stream(input_form):
    _, ingest = _stream(_dataset(input_form, _chunks(6)))
    assert all(not chip.in_flight for chip in ingest.chips)
    assert not ingest.kept


@pytest.mark.parametrize("input_form", ("callable", "iterator"))
def test_a_failing_source_leaves_no_batch_behind(input_form):
    chunks = _chunks(6)

    def rows():
        yield chunks[0]
        raise OSError("the partition went away")

    dataset = rows if input_form == "callable" else rows()
    ingest = streaming.IngestTrace()
    source = BatchSource(dataset, batch_rows=BATCH)
    with pytest.raises(OSError):
        streaming.stream_covariance(source, ingest=ingest)
    assert ingest.counters["batches"] > 0
    assert all(not chip.in_flight for chip in ingest.chips)


def test_hand_fed_stream_lets_go_at_finalize(monkeypatch):
    log = _Log(monkeypatch)
    fed = DistributedStreamingPCA(N, data_mesh(2))
    (x,) = _chunks(1)
    for _ in range(6):  # three a chip
        fed.partial_fit(x)
    chips = fed._ingest.chips
    assert [len(chip.in_flight) for chip in chips] == [2, 2]
    assert len(log.waits()) == 2  # each chip's third waited for its first
    log.assert_each_wait_is_for_the_chips_put_before_last()
    fed.finalize(K)
    assert [len(chip.in_flight) for chip in chips] == [0, 0]
    assert fed.rows_seen == 6 * BATCH
