"""Host batches cross the link in slices (CPU).

``IngestTrace.batches`` cuts each host batch the source yields into
⌈nbytes ÷ ``PUT_BYTES_MAX``⌉ equal row slices (views, each with its slice of
the mask), and from there on a slice is what every consumer puts, keeps,
replays and sums. At 1 GiB nothing a CPU suite makes is cut, so the tests
hold ``PUT_BYTES_MAX`` down to a few KiB. What must hold: the sliced fit is
the whole-batch fit to float32 rounding in every branch (one pass, two
passes with the shift accepted or refused, several chips, a padded tail),
the counters say the mechanism engaged and nothing else moved, a batch
whose rows suit the Pallas kernel is never cut off its row block, a staging
buffer goes back only once its last slice has landed, and a Lloyd fit
returns the same centres.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from shift_rows import mirrored_pairs, verdict

from spark_rapids_ml_tpu import KMeans, PCA
from spark_rapids_ml_tpu.data.batches import BatchSource
from spark_rapids_ml_tpu.ops import streaming
from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_R

N, BATCH, K = 16, 64, 3
BATCH_NBYTES = BATCH * N * 4  # float32 rows


def _slices(monkeypatch, pieces: int) -> None:
    """Cut a ``BATCH`` × ``N`` float32 batch into ``pieces`` slices."""
    monkeypatch.setattr(streaming, "PUT_BYTES_MAX", BATCH_NBYTES // pieces)


def _rows(rows: int, seed: int = 4, sort: bool = False) -> np.ndarray:
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, N)) * np.linspace(2.0, 0.5, N) + 3.0
    if sort:
        x = x[np.argsort(x[:, 0])]
    return x.astype(np.float32)


def _stream(dataset, chips=None):
    device = None if chips is None else tuple(jax.local_devices()[:chips])
    ingest = streaming.IngestTrace(device=device)
    source = BatchSource(dataset, batch_rows=BATCH)
    cov, mean, count = streaming.stream_covariance(
        source, dtype=jnp.float32, ingest=ingest)
    cov = np.asarray(jax.block_until_ready(cov))
    ingest.all_landed()
    return (cov, np.asarray(mean), int(count)), ingest.counters


def _close(a, b) -> None:
    """Two fits' (covariance, mean, count) alike to float32 rounding."""
    scale = np.max(np.abs(a[0]))
    assert np.max(np.abs(a[0] - b[0])) <= 2e-5 * scale
    np.testing.assert_allclose(a[1], b[1], rtol=2e-6, atol=2e-6)
    assert a[2] == b[2]


# name → (rows, input form, rows sorted by a feature, chips, accepted)
FITS = {
    "one_pass": (4 * BATCH, "iterator", False, None, None),
    "two_pass_accepted": (4 * BATCH, "callable", False, None, True),
    "two_pass_refused": (4 * BATCH, "callable", True, None, False),
    "padded_tail": (3 * BATCH + 10, "callable", True, None, False),
    "two_chips": (6 * BATCH, "callable", False, 2, True),
    "four_chips_tail": (8 * BATCH + 6, "iterator", False, 4, None),
}


def _dataset(name: str):
    rows, form, sort, _, accepted = FITS[name]
    x = _rows(rows, sort=sort)
    if accepted:
        (x,) = mirrored_pairs([x], 3.0)
    chunks = [x[: rows // 2], x[rows // 2:]]
    return x, ((lambda: list(chunks)) if form == "callable"
               else (lambda: iter(chunks)))


@pytest.mark.parametrize("pieces", (2, 4))
@pytest.mark.parametrize("name", sorted(FITS))
def test_a_sliced_fit_is_the_whole_batch_fit(monkeypatch, name, pieces):
    x, make = _dataset(name)
    chips = FITS[name][3]
    whole, w = _stream(make(), chips)
    _slices(monkeypatch, pieces)
    sliced, s = _stream(make(), chips)
    _close(sliced, whole)
    assert sliced[2] == x.shape[0]
    accepted = FITS[name][4]
    if accepted is not None:
        assert verdict(s) == verdict(w) == (accepted, 1 if accepted else 2)
    # the mechanism engaged, and nothing else that crossed moved
    assert s["batches"] == pieces * w["batches"]
    assert s["host_batches"] == w["host_batches"] == w["batches"]
    assert sum(chip["landings"] for chip in s["per_chip"]) == s["batches"]
    for key in ("bytes_put", "rows_put", "passes"):
        assert s[key] == w[key], key
    assert s["puts_in_flight_max"] <= streaming.PUTS_IN_FLIGHT
    assert isinstance(s["landing_tail_seconds"], float)


def test_a_padded_tail_slice_of_padding_only_shifts_nothing(monkeypatch):
    """Ten rows, one padded batch: its second slice holds padding only and
    is the first put of the second chip, which then has no rows to take a
    shift from — its Gram stays 0 about 0, not NaN about 0/0, and the
    fit's shift stands."""
    (x,) = mirrored_pairs([_rows(10)], 3.0)
    _slices(monkeypatch, 2)
    got, c = _stream(lambda: [x], chips=2)
    assert c["per_chip"][1]["rows"] == 0 and c["batches"] == 2
    assert verdict(c) == (True, 1)
    _close(got, _stream(lambda: [x])[0])


def test_the_source_count_and_the_slices_counts_on_a_fit(monkeypatch):
    _slices(monkeypatch, 4)
    x = _rows(5 * BATCH + 7)
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").fit(iter([x]))
    c = model.fit_report_.extra["ingest"]
    assert (c["host_batches"], c["batches"]) == (6, 24)
    assert c["rows_put"] == 6 * BATCH and model.fit_report_.rows == x.shape[0]
    assert c["accumulate_calls"]["xla"] == 24


# -- the Pallas row block ------------------------------------------------------

@pytest.mark.parametrize("rows,pieces,cut", [
    (4 * _BLOCK_R, 2, 2), (4 * _BLOCK_R, 4, 4),
    (2 * _BLOCK_R, 4, 1),  # 512-row slices would leave the kernel: whole
    (3 * _BLOCK_R, 2, 1),  # 1536-row slices: whole
    (96, 3, 3),  # rows the kernel never takes: cut as asked
    (100, 3, 1),  # not even: whole
])
def test_a_pallas_aligned_batch_is_never_cut_off_the_row_block(
        monkeypatch, rows, pieces, cut):
    nbytes = rows * 4096 * 4
    monkeypatch.setattr(streaming, "PUT_BYTES_MAX", -(-nbytes // pieces))
    assert streaming.put_slices(rows, nbytes) == cut


def test_a_batch_at_the_limit_crosses_whole():
    limit = streaming.PUT_BYTES_MAX
    assert limit == 1 << 30
    assert streaming.put_slices(65536, limit) == 1
    assert streaming.put_slices(131072, 2 * limit) == 2  # 2 GiB at 4096
    assert streaming.put_slices(4096, 128 << 20) == 1  # the default batch


def test_the_slices_are_views_of_the_batch(monkeypatch):
    monkeypatch.setattr(streaming, "STAGING", streaming.StagingPool())
    _slices(monkeypatch, 2)
    x = _rows(BATCH + 10)
    ingest = streaming.IngestTrace()
    out = list(ingest.batches(BatchSource([x], batch_rows=BATCH)))
    assert [piece.shape[0] for piece, _ in out] == [BATCH // 2] * 4
    assert all(piece.base is not None for piece, _ in out)
    masks = [m for _, m in out]
    assert masks[:2] == [None, None]
    assert masks[2].sum() == 10 and not masks[3].any()
    assert ingest.counters["host_batches"] == 2


# -- staging: lent again only once every slice has landed ----------------------

class _Chip:
    """A chip stand-in (``put_copies`` yes, a copying ``device_put``, a
    pool of the test's own) logging ("lend", buffer), ("put", x_dev,
    buffer or None) and ("wait", x_dev) in order."""

    def __init__(self, monkeypatch):
        self.events = []
        self.pool = streaming.StagingPool()
        monkeypatch.setattr(streaming, "STAGING", self.pool)
        monkeypatch.setattr(streaming, "put_copies", lambda device: True)
        device_put = jax.device_put
        monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: device_put(
            x.copy() if isinstance(x, np.ndarray) else x, *a, **k))
        staging, put = streaming.IngestTrace.staging, streaming.IngestTrace.put

        def logged_staging(ingest, shape, dtype):
            buffer = staging(ingest, shape, dtype)
            self.events.append(("lend", buffer))
            return buffer

        def logged_put(ingest, batch, mask, dtype):
            c, x_dev, m_dev = put(ingest, batch, mask, dtype)
            self.events.append(("put", x_dev, ingest.chips[c].in_flight[-1][1]))
            return c, x_dev, m_dev

        monkeypatch.setattr(streaming.IngestTrace, "staging", logged_staging)
        monkeypatch.setattr(streaming.IngestTrace, "put", logged_put)
        monkeypatch.setattr(streaming, "wait_for_landing",
                            lambda x_dev: self.events.append(("wait", x_dev)))

    def lends_of_buffers_read_before(self) -> int:
        """Asserts no buffer is lent while ANY put of a slice of it has not
        been waited for; returns how many lends were of a buffer read
        before."""
        reading = {}  # id(buffer) → ids of its slices' puts not waited for
        read_before, again = set(), 0
        for event in self.events:
            if event[0] == "put" and event[2] is not None:
                reading.setdefault(id(event[2]), set()).add(id(event[1]))
                read_before.add(id(event[2]))
            elif event[0] == "wait":
                for puts in reading.values():
                    puts.discard(id(event[1]))
            elif event[0] == "lend":
                assert not reading.get(id(event[1])), "lent while read"
                again += id(event[1]) in read_before
        return again


def _ragged(x: np.ndarray, sizes=(7, 13, 10, 21, 5)) -> list:
    chunks, start, i = [], 0, 0
    while start < x.shape[0]:
        chunks.append(x[start:start + sizes[i % len(sizes)]])
        start += sizes[i % len(sizes)]
        i += 1
    return chunks


@pytest.mark.parametrize("pieces", (2, 4))
@pytest.mark.parametrize("chips", (1, 2))
@pytest.mark.parametrize("passes", (1, 2))
def test_a_buffer_goes_back_only_after_its_last_slice_landed(
        monkeypatch, passes, chips, pieces):
    chip = _Chip(monkeypatch)
    _slices(monkeypatch, pieces)
    x = _rows(7 * BATCH + 11)
    chunks = _ragged(x)
    dataset = iter(chunks) if passes == 1 else (lambda: list(chunks))
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").setNumDevices(chips).fit(dataset)
    c = model.fit_report_.extra["ingest"]
    assert c["batches"] == pieces * c["host_batches"] \
        == pieces * c["batches_copied"]
    assert chip.lends_of_buffers_read_before() > 0  # the buffers cycled
    # every slice of a buffer was put from it
    puts_from = [id(e[2]) for e in chip.events if e[0] == "put"]
    assert all(puts_from.count(b) % pieces == 0 for b in set(puts_from))
    # the fit's end handed back the window's last: every buffer is home
    assert len(chip.pool.free()) == c["staging_fresh"]
    whole = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").setNumDevices(chips).fit(
            iter([x]) if passes == 1 else (lambda: [x]))
    np.testing.assert_allclose(model.mean, whole.mean, rtol=1e-6)


def test_a_slice_passed_over_in_replay_frees_its_buffer_with_its_sibling(
        monkeypatch):
    """Pass 2 over a budget that kept three slices: the host batch whose
    first slice was kept and second was not is put half; its buffer goes
    back once that half has landed, and a batch passed over whole is free
    at once."""
    chip = _Chip(monkeypatch)
    _slices(monkeypatch, 2)
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda *a, **k: 3 * BATCH_NBYTES // 2)
    x = _rows(4 * BATCH, sort=True)  # refuses the shift: pass 2 runs
    model = PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").fit(lambda: _ragged(x))
    c = model.fit_report_.extra["ingest"]
    assert c["batches_kept"] == 3 and c["passes"] == 2
    assert c["batches"] == 8 + 5
    chip.lends_of_buffers_read_before()
    assert len(chip.pool.free()) == c["staging_fresh"]


def test_a_cast_frees_each_slice_at_once(monkeypatch):
    chip = _Chip(monkeypatch)
    _slices(monkeypatch, 2)
    pieces = _ragged(_rows(4 * BATCH).astype(np.float64))
    c = PCA().setK(K).set("batchRows", BATCH).set("dtype", "float32").fit(
        iter(pieces)).fit_report_.extra["ingest"]
    assert (c["staging_fresh"], c["staging_reused"]) == (1, 3)
    assert all(e[2] is None for e in chip.events if e[0] == "put")


# -- Lloyd ---------------------------------------------------------------------

def test_kmeans_returns_the_same_centres_with_and_without_slices(
        monkeypatch):
    """The benchmark's KMeans configuration at its CPU cut: 64 features, k
    16, 4096-row batches of two 8192-row partitions."""
    n, k, rows = 64, 16, 16384
    rng = np.random.default_rng(41)
    centres = rng.normal(size=(k, n))
    x = (centres[rng.integers(0, k, rows)]
         + 1.26 * rng.normal(size=(rows, n))).astype(np.float32)
    parts = [x[:rows // 2], x[rows // 2:]]

    def fit():
        return KMeans().setK(k).setSeed(1).setMaxIter(6).setTol(1e-20).set(
            "initMode", "random").set("batchRows", 4096).set(
                "dtype", "float32").fit(lambda: list(parts))

    whole = fit()
    monkeypatch.setattr(streaming, "PUT_BYTES_MAX", 4096 * n * 4 // 2)
    sliced = fit()
    ingest = sliced.fit_report_.extra["ingest"]
    assert ingest["batches"] == 2 * whole.fit_report_.extra["ingest"][
        "batches"]
    np.testing.assert_array_equal(sliced.initial_centers_,
                                  whole.initial_centers_)
    assert sliced.n_iter_ == whole.n_iter_
    np.testing.assert_allclose(sliced.cluster_centers, whole.cluster_centers,
                               rtol=0, atol=1e-5)
