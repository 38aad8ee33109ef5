"""Staging buffers of re-blocking: lent, and taken back at the landing (CPU).

A device batch assembled from several chunks is written into an array the
fit's ``IngestTrace`` lends out of the process's ``streaming.STAGING`` pool,
and the array goes back when the put that read it has been seen to land
(``wait_for_landing`` in the put window, ``all_landed`` at the fit's end).

The CPU backend is not a chip in the one respect this rests on:
``device_put`` there may make the host array the device array (see
``test_a_landed_put_no_longer_reads_the_host_array``), so
``streaming.put_copies`` says no for it and a CPU fit never gets a buffer
back. The ``chip`` fixture stands in for the chip the way
``test_streaming_window.py`` does for the wait: ``put_copies`` says yes,
``jax.device_put`` copies what it is handed, the pool is a new one, and
every lend, put and wait is logged in order.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.batches import BatchSource, streamed_reduce
from spark_rapids_ml_tpu.ops import streaming

N, BATCH, K = 24, 32, 3
FULL = 6  # whole batches in the rows below; then a masked tail
TAIL = 11
ROWS = FULL * BATCH + TAIL
COPIED = FULL + 1  # every batch of a ragged hand-over is assembled
MOST = streaming.PUTS_IN_FLIGHT + 1  # staging buffers a chip cycles


def _rows(seed: int = 35, rows: int = ROWS, dtype=np.float32) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(rows, N)) + 3.0 * (np.arange(N) % 4)).astype(
        dtype)


def _ragged(x: np.ndarray, sizes=(7, 13, 10, 21, 5)) -> list:
    """``x`` cut into chunks shorter than a batch, so that no batch is a
    slice of one chunk."""
    chunks, start, i = [], 0, 0
    while start < x.shape[0]:
        chunks.append(x[start:start + sizes[i % len(sizes)]])
        start += sizes[i % len(sizes)]
        i += 1
    return chunks


def _record_batches(x: np.ndarray, record_rows: int = 10) -> list:
    import pyarrow as pa

    values = pa.array(x.reshape(-1))
    offsets = pa.array(np.arange(0, (x.shape[0] + 1) * N, N, dtype=np.int32))
    rows = pa.ListArray.from_arrays(offsets, values)
    return [pa.RecordBatch.from_arrays([rows.slice(s, record_rows)],
                                       names=["features"])
            for s in range(0, x.shape[0], record_rows)]


HAND_OVERS = {"numpy": _ragged, "arrow": _record_batches}


def _dataset(pieces: list, passes: int):
    """One-pass: a one-shot iterator; two-pass: a callable, re-iterable."""
    return iter(pieces) if passes == 1 else (lambda: list(pieces))


def _fit(pieces, passes: int = 1, chips: int = 1, batch: int = BATCH):
    """``pieces``: a list of chunks, or a dataset as it is."""
    dataset = _dataset(pieces, passes) if isinstance(pieces, list) else pieces
    return PCA().setK(K).set("batchRows", batch).set(
        "dtype", "float32").setNumDevices(chips).fit(dataset)


def _ingest(model) -> dict:
    return model.fit_report_.extra["ingest"]


def _same_model(a, b) -> bool:
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in ("pc", "explained_variance", "mean"))


class _Chip:
    """The stand-in's log: ("lend", buffer) | ("put", x_dev, staged) |
    ("wait", x_dev), in the order they happened."""

    def __init__(self, monkeypatch):
        self.events = []
        self.pool = streaming.StagingPool()
        monkeypatch.setattr(streaming, "STAGING", self.pool)
        monkeypatch.setattr(streaming, "put_copies", lambda device: True)
        device_put = jax.device_put

        def copying_put(x, *args, **kwargs):
            # a chip copies the batch into memory of its own
            return device_put(x.copy() if isinstance(x, np.ndarray) else x,
                              *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", copying_put)
        staging, put = streaming.IngestTrace.staging, streaming.IngestTrace.put

        def logged_staging(ingest, shape, dtype):
            buffer = staging(ingest, shape, dtype)
            self.events.append(("lend", buffer))
            return buffer

        def logged_put(ingest, batch, mask, dtype):
            c, x_dev, m_dev = put(ingest, batch, mask, dtype)
            assert ingest.chips[c].in_flight[-1][0] is x_dev
            self.events.append(("put", x_dev, ingest.chips[c].in_flight[-1][1]))
            return c, x_dev, m_dev

        monkeypatch.setattr(streaming.IngestTrace, "staging", logged_staging)
        monkeypatch.setattr(streaming.IngestTrace, "put", logged_put)
        monkeypatch.setattr(streaming, "wait_for_landing",
                            lambda x_dev: self.events.append(("wait", x_dev)))

    def unlanded(self) -> list:
        """Staging buffers of the puts no wait has been for."""
        waited = {id(e[1]) for e in self.events if e[0] == "wait"}
        return [e[2] for e in self.events if e[0] == "put"
                and e[2] is not None and id(e[1]) not in waited]

    def assert_no_buffer_is_lent_while_a_put_reads_it(self) -> int:
        """Walks the log; returns how many lends were of a buffer that a
        put had read before (each after the wait for that put)."""
        reading = {}  # id(staging buffer) → the device batch put from it
        put_before, again = set(), 0
        for event in self.events:
            if event[0] == "put" and event[2] is not None:
                reading[id(event[2])] = event[1]
                put_before.add(id(event[2]))
            elif event[0] == "wait":
                reading = {b: x for b, x in reading.items()
                           if x is not event[1]}
            elif event[0] == "lend":
                assert id(event[1]) not in reading
                again += id(event[1]) in put_before
        return again


@pytest.fixture
def chip(monkeypatch):
    return _Chip(monkeypatch)


def _keep(monkeypatch, batches: int) -> None:
    def rule(device, reserve_nbytes):
        # the Gram loop's reserve: three (BATCH, N) batches and three N x N
        # of the device's dtype
        itemsize = reserve_nbytes // (3 * BATCH * N + 3 * N * N)
        return batches * BATCH * N * itemsize

    monkeypatch.setattr(streaming, "keep_budget_bytes", rule)


# -- (a) lent again only after the wait on the put that read it ---------------

# name → (passes, chips, batches of pass 1 each chip keeps)
WALKS = {
    "one_chip": (1, 1, 0),
    "one_chip_two_pass": (2, 1, 0),
    "four_chips": (1, 4, 0),
    "four_chips_two_pass": (2, 4, 0),
    "partly_kept_replay": (2, 1, 2),  # pass 2 passes over two, puts five
    "partly_kept_replay_four_chips": (2, 4, 1),
    "all_but_the_tail_kept": (2, 1, FULL),
}


@pytest.mark.parametrize("hand_over", ("numpy", "arrow"))
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_a_buffer_is_lent_again_only_after_its_put_was_waited_for(
        monkeypatch, chip, walk, hand_over):
    passes, chips, kept = WALKS[walk]
    _keep(monkeypatch, kept)
    rows = 4 * ROWS if chips == 4 else ROWS  # seven batches a chip
    pieces = HAND_OVERS[hand_over](_rows(rows=rows))
    model = _fit(pieces, passes, chips)
    c = _ingest(model)
    assert c["batches_kept"] == kept * chips
    assert c["batches_copied"] == passes * (rows // BATCH + 1)
    again = chip.assert_no_buffer_is_lent_while_a_put_reads_it()
    # the buffers did cycle: no chip ever needed more than its three
    assert c["staging_fresh"] <= MOST * chips
    assert c["staging_reused"] + c["staging_fresh"] == c["batches_copied"]
    assert again > 0
    # the fit's end handed back the window's last: every buffer made is
    # in the pool again
    assert len(chip.pool.free()) == c["staging_fresh"]
    assert _same_model(model, _fit([_rows(rows=rows)], passes, chips))


def test_a_batch_passed_over_in_replay_is_free_at_the_next_next(
        monkeypatch, chip):
    _keep(monkeypatch, 3)
    model = _fit(_ragged(_rows()), passes=2)
    c = _ingest(model)
    assert c["batches_kept"] == 3 and c["batches"] == COPIED + COPIED - 3
    lends = [e[1] for e in chip.events if e[0] == "lend"]
    assert len(lends) == 2 * COPIED
    # pass 2's first three batches were assembled and never put: each was
    # handed out again for the very next batch
    first = lends[COPIED]
    assert all(b is first for b in lends[COPIED:COPIED + 4])
    put_from = [e[2] for e in chip.events if e[0] == "put"]
    assert put_from[COPIED] is first  # pass 2's first put, its fourth batch
    assert c["staging_fresh"] <= MOST


def test_a_cast_in_put_frees_the_buffer_at_once(chip):
    # float64 rows into a float32 fit: ``put`` makes a new array of each
    pieces = _ragged(_rows(dtype=np.float64))
    c = _ingest(_fit(pieces))
    assert (c["staging_fresh"], c["staging_reused"]) == (1, COPIED - 1)
    assert all(e[2] is None for e in chip.events if e[0] == "put")
    assert [b.dtype for b in chip.pool.free()] == [np.float64]


# -- (b) the model is the whole-chunk fit's, the pool cold or warm ------------

@pytest.mark.parametrize("chips", (1, 4))
@pytest.mark.parametrize("passes", (1, 2))
@pytest.mark.parametrize("hand_over", ("numpy", "arrow"))
def test_model_is_bit_equal_to_the_whole_chunk_fit_pool_cold_or_warm(
        chip, hand_over, passes, chips):
    x = _rows(seed=7 + passes)
    whole = _fit([x], passes, chips)
    assert _ingest(whole)["batches_viewed"] == passes * FULL
    pieces = HAND_OVERS[hand_over](x)
    for nth in range(3):
        model = _fit(pieces, passes, chips)
        assert _same_model(model, whole), f"fit {nth}"
        assert _ingest(model)["batches_copied"] == passes * COPIED
    # the third fit found every buffer it asked for
    assert _ingest(model)["staging_fresh"] == 0


@pytest.mark.parametrize("passes", (1, 2))
def test_model_is_bit_equal_on_the_cpu_as_it_is(monkeypatch, passes):
    """No stand-in but a pool of the test's own: the CPU keeps every buffer
    it is handed, so every copied batch is written into a new array, as
    before there was a pool."""
    pool = streaming.StagingPool()
    monkeypatch.setattr(streaming, "STAGING", pool)
    x = _rows(seed=9)
    whole = _fit([x], passes)
    for _ in range(2):
        model = _fit(_ragged(x), passes)
        assert _same_model(model, whole)
        c = _ingest(model)
        assert (c["staging_reused"], c["staging_fresh"]) == (
            0, passes * COPIED)
    assert pool.free() == []


# -- (c) the counters ---------------------------------------------------------

@pytest.mark.parametrize("hand_over", ("numpy", "arrow"))
def test_the_first_fit_makes_at_most_three_and_the_second_none(chip,
                                                               hand_over):
    pieces = HAND_OVERS[hand_over](_rows())
    first, second = (_ingest(_fit(pieces)) for _ in range(2))
    assert first["batches_copied"] == second["batches_copied"] == COPIED
    assert first["staging_fresh"] == MOST
    assert first["staging_reused"] == COPIED - MOST
    assert (second["staging_reused"], second["staging_fresh"]) == (COPIED, 0)
    for c in (first, second):
        # every row written once: whole batches, then the tail's rows
        assert c["bytes_reblocked"] == ROWS * N * 4
        assert c["batches_viewed"] == 0
    assert len(chip.pool.free()) == MOST


@pytest.mark.parametrize("passes", (1, 2))
def test_aligned_chunks_ask_for_nothing(chip, passes):
    x = _rows(rows=FULL * BATCH)
    c = _ingest(_fit([x[:2 * BATCH], x[2 * BATCH:]], passes))
    assert c["batches_viewed"] == passes * FULL
    assert (c["staging_reused"], c["staging_fresh"]) == (0, 0)
    assert c["batches_copied"] == 0 and c["bytes_reblocked"] == 0
    assert chip.pool.free() == [] and not [
        e for e in chip.events if e[0] == "lend"]


# -- (d) a walk with no trace never sees a buffer twice -----------------------

def test_an_untraced_walk_gets_new_arrays(chip):
    pieces = _ragged(_rows())
    _fit(pieces)  # the pool is warm, at this very shape
    free = chip.pool.free()
    assert len(free) == MOST
    source = BatchSource(lambda: iter(pieces), batch_rows=BATCH)
    batches = [b for b, _ in source.batches()]
    assert len(batches) == COPIED and source.trace is None
    for i, b in enumerate(batches):
        assert not any(np.shares_memory(b, other)
                       for other in batches[:i] + free)
    assert np.array_equal(np.concatenate(batches)[:ROWS], _rows())
    assert [id(b) for b in chip.pool.free()] == [id(b) for b in free]


def test_streamed_reduce_hands_the_reducer_rows_that_stay(chip):
    x = _rows(dtype=np.float64)  # float64: ``rows`` is the batch itself
    _fit(_ragged(x))
    free = chip.pool.free()
    seen = []
    streamed_reduce(BatchSource(iter(_ragged(x)), batch_rows=BATCH),
                    lambda acc, rows: seen.append(rows))
    assert np.array_equal(np.concatenate(seen), x)
    for i, rows in enumerate(seen):
        assert not any(np.shares_memory(rows, other)
                       for other in seen[:i] + free)


# -- (e) a fit that dies drops what it has not seen land ----------------------

@pytest.mark.parametrize("passes", (1, 2))
def test_a_dying_fit_drops_the_buffers_of_unlanded_puts(chip, passes):
    x = _rows()
    pieces = _ragged(x)
    whole = _fit([x], passes)
    _fit(pieces, passes)
    assert len(chip.pool.free()) == MOST
    del chip.events[:]

    def dying():
        yield from pieces[:len(pieces) * 2 // 3]
        raise OSError("the partition went away")

    with pytest.raises(OSError):
        _fit(dying if passes == 2 else dying())
    unlanded = chip.unlanded()
    assert len(unlanded) == streaming.PUTS_IN_FLIGHT
    free = chip.pool.free()
    assert len(free) == MOST - streaming.PUTS_IN_FLIGHT
    assert not any(b is u for b in free for u in unlanded)
    chip.assert_no_buffer_is_lent_while_a_put_reads_it()
    after = _fit(pieces, passes)
    assert _same_model(after, whole)
    assert _ingest(after)["staging_fresh"] == streaming.PUTS_IN_FLIGHT
    assert len(chip.pool.free()) == MOST


# -- (f) one shape, and a bound -----------------------------------------------

@pytest.mark.parametrize("chips", (1, 4))
def test_the_pool_holds_one_shape_and_three_buffers_a_chip(chip, chips):
    x = _rows(rows=4 * ROWS)
    _fit(_ragged(x), chips=chips)
    held = chip.pool.free()
    assert {b.shape for b in held} == {(BATCH, N)}
    assert 0 < len(held) <= MOST * chips
    c = _ingest(_fit(_ragged(x), chips=chips, batch=BATCH // 2))
    assert c["staging_fresh"] <= MOST * chips  # none of the old ones served
    held = chip.pool.free()
    assert {(b.shape, b.dtype) for b in held} == {
        ((BATCH // 2, N), np.dtype(np.float32))}
    assert 0 < len(held) <= MOST * chips
    # a smaller fit at the same shape lets go of what it cannot use
    _fit(_ragged(x), chips=1, batch=BATCH // 2)
    assert len(chip.pool.free()) <= MOST


def test_the_pool_is_made_empty_and_the_process_has_one():
    assert streaming.StagingPool().free() == []
    assert isinstance(streaming.STAGING, streaming.StagingPool)
    a, had = streaming.StagingPool().lend((4, 2), np.float32, MOST)
    assert not had and a.shape == (4, 2) and a.dtype == np.float32


def test_take_back_keeps_only_the_shape_last_asked_for_and_only_so_many():
    pool = streaming.StagingPool()
    a, _ = pool.lend((4, 2), np.float32, 2)
    b, _ = pool.lend((4, 2), np.float32, 2)
    c, _ = pool.lend((4, 2), np.float32, 2)
    other, _ = pool.lend((4, 2), np.float64, 2)
    pool.take_back(a, 2)  # the pool has moved on to float64
    assert pool.free() == []
    pool.take_back(other, 2)
    again, had = pool.lend((4, 2), np.float64, 2)
    assert had and again is other
    d, _ = pool.lend((4, 2), np.float32, 2)
    for buffer in (a, b, c, d):
        pool.take_back(buffer, 2)
    assert [id(x) for x in pool.free()] == [id(a), id(b)]


def test_threads_never_hold_one_buffer_at_once():
    """Fits on several threads share the pool: a buffer lent is nobody
    else's until it is taken back."""
    import sys

    pool, held, lock, clashes = streaming.StagingPool(), set(), \
        threading.Lock(), []
    stop = threading.Event()

    def borrow():
        while not stop.is_set():
            buffer, _ = pool.lend((8, 2), np.float32, 4)
            with lock:
                if id(buffer) in held:
                    clashes.append(id(buffer))
                held.add(id(buffer))
            buffer[:] = threading.get_ident() % 97
            with lock:
                held.discard(id(buffer))
            pool.take_back(buffer, 4)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=borrow) for _ in range(16)]
        for t in threads:
            t.start()
        stop.wait(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert clashes == [] and len(pool.free()) <= 4


# -- (g) what the take-back rests on ------------------------------------------

def _host_array(aligned: bool) -> np.ndarray:
    """A (64, 16) float32 array that starts on a 64-byte boundary, or 16
    bytes past one."""
    raw = np.empty(64 * 16 * 4 + 128, np.uint8)
    start = (-raw.ctypes.data) % 64 + (0 if aligned else 16)
    return raw[start:start + 64 * 16 * 4].view(np.float32).reshape(64, 16)


@pytest.mark.parametrize("aligned", (True, False))
def test_a_landed_put_no_longer_reads_the_host_array(aligned):
    """After ``device_put`` + ``block_until_ready`` a write to the host
    array must not reach the device array wherever ``put_copies`` says the
    buffer may be lent again. On this backend (the CPU, jax 0.9.0) it does
    reach it when the host array is 64-byte aligned — the device array *is*
    the host array — which is why ``put_copies`` says no here."""
    device = jax.local_devices()[0]
    host = _host_array(aligned)
    host[:] = 1.0
    x_dev = jax.device_put(host, device)
    streaming.wait_for_landing(x_dev)
    host[:] = 2.0
    reached = float(np.asarray(x_dev)[0, 0]) == 2.0
    if streaming.put_copies(device):
        assert not reached
    else:
        assert device.platform == "cpu"
        assert reached == aligned
