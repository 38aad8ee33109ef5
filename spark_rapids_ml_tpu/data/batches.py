"""Out-of-core batch sources: fixed-shape streams for unbounded datasets.

The reference never materializes the whole dataset in one buffer — it
streams partition chunks through the device, one JNI GEMM per partition
(``/root/reference/src/main/scala/org/apache/spark/ml/linalg/distributed/RapidsRowMatrix.scala:168-202``).
This module is the TPU-native ingestion contract behind that capability:
any fit() input — an in-memory matrix, a generator of arbitrarily-sized
chunks, or a callable producing such a generator — is normalized into a
stream of FIXED-shape ``(batch, mask)`` pairs. Fixed shapes matter because
XLA compiles one program per shape: uneven chunks are re-blocked into
``batch_rows``-row buckets and the tail is padded + masked, so the whole
stream hits one cached executable (SURVEY.md §7 "bucketed static shapes").

Re-iterability drives semantics upstream: a re-iterable source (matrix,
list of chunks, or factory callable) supports the exact two-pass
mean-then-centered-Gram schedule; a one-shot iterator gets the one-pass
(Σxxᵀ, Σx, n) formulation (documented cancellation hazard for |μ| ≫ σ,
see ``ops/covariance.covariance_from_stats``).
"""

from __future__ import annotations

import contextlib
import itertools
import os
from typing import Iterator, Optional, Tuple

import numpy as np

# In-memory inputs larger than this stream through the device accumulator in
# batch_rows buckets instead of one whole-matrix device_put (LinearRegression
# and KMeans; PCA streams every input and does not ask). Default 1 GiB:
# comfortably under a v5e chip's HBM while keeping small fits single-shot.
STREAM_THRESHOLD_ENV = "TPUML_STREAM_THRESHOLD_BYTES"
DEFAULT_STREAM_THRESHOLD = 1 << 30


def stream_threshold_bytes() -> int:
    value = os.environ.get(STREAM_THRESHOLD_ENV)
    if value is None:
        return DEFAULT_STREAM_THRESHOLD
    try:
        return int(value)
    except ValueError as exc:
        raise ValueError(
            f"{STREAM_THRESHOLD_ENV}={value!r} is not an integer byte count"
        ) from exc


def auto_batch_rows(n_features: int, target_bytes: int = 128 << 20,
                    itemsize: int = 4) -> int:
    """Rows per device batch so one f32 batch is ~``target_bytes``, rounded
    to a multiple of 256 (MXU/lane-friendly), floored at 1024."""
    rows = max(1024, target_bytes // max(1, n_features * itemsize))
    return max(1024, (rows // 256) * 256)


# What a walk over a source counts (``fit_report_.extra["ingest"]`` of a
# streamed fit): the chunks as they arrive and whether reading one left its
# rows where they were (``chunks_viewed``) or wrote them into a new array
# (``chunks_copied``: a list densified, sparse rows filled in), and the
# fixed-shape batches as they leave — slices of one chunk
# (``batches_viewed``) or assembled from several / padded, by a host copy of
# ``bytes_reblocked`` bytes (``batches_copied``). The array a copied batch is
# written into is the trace's to give (``staging``), and the trace counts
# whether it had been written before (``staging_reused``) or was made for
# this batch (``staging_fresh``).
SOURCE_COUNTERS = {
    "chunks": 0, "chunk_rows_min": None, "chunk_rows_max": 0,
    "chunks_viewed": 0, "chunks_copied": 0,
    "batches_viewed": 0, "batches_copied": 0, "bytes_reblocked": 0,
    "staging_reused": 0, "staging_fresh": 0,
}


class _Untraced:
    """What ``BatchSource.batches`` reports to when nobody listens: the
    interface ``ops.streaming.IngestTrace`` gives it."""

    def __init__(self):
        self.counters = dict(SOURCE_COUNTERS)

    def next_stage(self, part: str):
        return contextlib.nullcontext()

    def staging(self, shape, dtype) -> np.ndarray:
        """The array to assemble a copied batch into: a new one every time.
        Nobody says when such a walk's caller is done with a batch, so no
        caller ever sees a buffer twice."""
        return np.empty(shape, dtype)


def columnar_chunks(input_col: Optional[str] = None):
    """``chunk_transform`` that reads a columnar chunk (a
    ``pyarrow.RecordBatch`` or ``Table``) through ``data.arrow
    .column_to_matrix`` and leaves every other chunk alone."""

    def read(chunk):
        from spark_rapids_ml_tpu.data.arrow import column_to_matrix, is_columnar

        return column_to_matrix(chunk, input_col) if is_columnar(chunk) \
            else chunk

    return read


_EXHAUSTED = object()  # what a source's iterator gives when it has no more


class ColumnBlocks:
    """A chunk whose rows are side-by-side column blocks of one height —
    a labelled chunk's X and y — joined only where a batch is written
    (``BatchSource``): the join is the re-blocking's one host copy into
    the batch, never a copy of the whole chunk first. ``viewed``: the
    blocks are the caller's arrays or views of them."""

    def __init__(self, blocks, dtype, viewed: bool = True):
        self.blocks = tuple(blocks)
        self.dtype = np.dtype(dtype)
        self.viewed = viewed
        self.shape = (self.blocks[0].shape[0],
                      sum(block.shape[1] for block in self.blocks))

    def __getitem__(self, rows: slice) -> "ColumnBlocks":
        return ColumnBlocks([block[rows] for block in self.blocks],
                            self.dtype, self.viewed)

    def write_into(self, out: np.ndarray) -> None:
        """The joined rows into ``out`` (this chunk's shape), cast to its
        dtype."""
        col = 0
        for block in self.blocks:
            out[:, col:col + block.shape[1]] = block
            col += block.shape[1]


def _as_chunk(chunk) -> np.ndarray:
    arr = np.asarray(chunk)
    if arr.ndim == 1:
        arr = arr[None, :]
    if arr.ndim != 2:
        raise ValueError(
            f"batch chunks must be 1-D or 2-D row arrays, got ndim={arr.ndim}"
        )
    return arr


def streaming_source(dataset, batch_rows: int = 0,
                     input_col: Optional[str] = None
                     ) -> Optional["BatchSource"]:
    """Return a BatchSource for inherently-streaming fit() inputs (a
    generator / iterator of chunks, or a zero-arg callable producing one),
    else None.

    A chunk may be columnar (a ``pyarrow.RecordBatch``: what Spark's
    ``mapInArrow`` hands a worker): its vector column ``input_col`` (None =
    its only column) is then read by ``data.arrow``. A ``RecordBatchReader``
    is an iterator of such chunks; a ``pyarrow.Table`` (or one
    ``RecordBatch``) streams as its record batches, re-iterable.

    Materializable inputs (arrays, frames, pandas, lists of vectors) return
    None — estimators decide separately whether to stream those by size.
    """
    import pandas as pd

    from spark_rapids_ml_tpu.data.arrow import is_columnar
    from spark_rapids_ml_tpu.data.frame import VectorFrame

    if isinstance(dataset, (VectorFrame, pd.DataFrame, np.ndarray, list, tuple)):
        return None
    if is_columnar(dataset):
        table = dataset
        dataset = getattr(table, "to_batches", lambda: [table])
    if not callable(dataset) and (hasattr(dataset, "__array__")
                                  or not hasattr(dataset, "__next__")):
        return None
    return BatchSource(dataset, batch_rows=batch_rows,
                       chunk_transform=columnar_chunks(input_col))


class BatchSource:
    """Normalizes a fit() input into fixed-shape ``(batch, mask)`` streams.

    ``source`` may be:
      * a 2-D array (or anything ``np.asarray`` densifies to one) — re-iterable,
      * a list/tuple of chunks — re-iterable,
      * a zero-arg callable returning an iterable of chunks — re-iterable
        (called once per pass),
      * a one-shot iterator/generator of chunks — single pass only.

    Chunks may have any row count; they are re-blocked into exact
    ``batch_rows`` buckets. Every yielded batch has shape
    ``(batch_rows, n_features)``; the final bucket is zero-padded with
    ``mask`` marking valid rows (``mask is None`` for full buckets — the
    jitted accumulators trace the mask-free fast path for those). A chunk
    a ``chunk_transform`` gives as ``ColumnBlocks`` (a labelled chunk's X
    and y) is joined into each batch by that batch's one host copy.
    """

    def __init__(self, source, batch_rows: int = 0,
                 n_features: Optional[int] = None, chunk_transform=None):
        """``chunk_transform`` (chunk → 2-D array) runs on each raw chunk
        BEFORE re-blocking — callers with structured chunks (e.g.
        LinearRegression's (X, y) pairs) pass it here instead of wrapping
        the source in a generator expression, which would defeat the
        non-fresh-factory detection below."""
        self._matrix: Optional[np.ndarray] = None
        self._factory = None
        self._oneshot: Optional[Iterator] = None
        self._transform = chunk_transform
        self.trace = None  # who ``batches()`` reports to: see there

        if callable(source):
            # A factory must produce a FRESH iterator per call. `lambda: gen`
            # over one generator object is an easy mistake that would make
            # pass 2 silently iterate an exhausted stream — detect it by
            # identity (same iterator object on both calls) and demote to a
            # one-shot source. `lambda: some_list` is fine: lists are not
            # their own iterators.
            probe = source()
            if iter(probe) is probe and source() is probe:
                self._oneshot = iter(probe)
            else:
                self._factory = source
        elif isinstance(source, (list, tuple)):
            chunks = [self._prep(c)[0] for c in source]
            self._factory = lambda: iter(chunks)
        elif hasattr(source, "__array__") or isinstance(source, np.ndarray):
            self._matrix = np.asarray(source)
            if self._matrix.ndim != 2:
                raise ValueError("matrix source must be 2-D")
        elif hasattr(source, "__next__") or hasattr(source, "__iter__"):
            self._oneshot = iter(source)
        else:
            raise TypeError(
                f"unsupported batch source {type(source).__name__}"
            )

        self._consumed = False
        self._first_pass_rows: Optional[int] = None
        self.n_features = n_features
        # (chunk, viewed) pairs read ahead of the first pass
        self._peeked: list = []
        if self._matrix is not None:
            self.n_features = self._matrix.shape[1]
        elif self.n_features is None:
            # Peek chunks up to the first that has rows to learn the width
            # (stashed and re-yielded): a columnar chunk of no rows has no
            # width to give.
            it = iter(self._factory() if self._factory else self._oneshot)
            peeked = []
            for chunk in it:
                peeked.append(self._prep(chunk))
                if peeked[-1][0].shape[0]:
                    break
            if not peeked:
                raise ValueError("batch source is empty")
            self.n_features = peeked[-1][0].shape[1]
            if self._factory is None:
                self._peeked = peeked
                self._oneshot = it
            # factory sources: the peek iterator is simply dropped; a fresh
            # pass re-produces every chunk.

        self.batch_rows = batch_rows if batch_rows > 0 else auto_batch_rows(
            self.n_features
        )
        if self._matrix is not None:
            self.batch_rows = min(self.batch_rows, max(1, self._matrix.shape[0]))

    @property
    def reiterable(self) -> bool:
        return self._matrix is not None or self._factory is not None

    def _prep(self, chunk) -> Tuple[np.ndarray, bool]:
        """(the chunk as a 2-D array, whether its rows stayed where they
        were): the array is the chunk itself or a view of another buffer
        (Arrow's, a reshape), not one made to hold its rows."""
        raw = chunk
        if self._transform is not None:
            chunk = self._transform(chunk)
        if isinstance(chunk, ColumnBlocks):
            return chunk, chunk.viewed
        arr = _as_chunk(chunk)
        return arr, arr is raw or not arr.flags.owndata or arr.size == 0

    def _read(self, chunks, trace) -> Iterator[Tuple[np.ndarray, bool]]:
        while True:
            with trace.next_stage("read"):
                chunk = next(chunks, _EXHAUSTED)
                if chunk is _EXHAUSTED:
                    return
                pair = self._prep(chunk)
            yield pair

    def _chunks(self, trace) -> Iterator[np.ndarray]:
        if self._matrix is not None:
            pairs = [(self._matrix, True)]
        elif self._factory is not None:
            pairs = self._read(iter(self._factory()), trace)
        else:
            if self._consumed:
                raise RuntimeError(
                    "one-shot batch source already consumed; pass a "
                    "callable returning a fresh iterator (or a matrix/list) "
                    "to allow multiple passes"
                )
            self._consumed = True
            peeked, self._peeked = self._peeked, []
            pairs = itertools.chain(peeked, self._read(self._oneshot, trace))
        counters = trace.counters
        for chunk, viewed in pairs:
            rows = chunk.shape[0]
            counters["chunks"] += 1
            counters["chunks_viewed" if viewed else "chunks_copied"] += 1
            counters["chunk_rows_max"] = max(counters["chunk_rows_max"], rows)
            least = counters["chunk_rows_min"]
            counters["chunk_rows_min"] = rows if least is None \
                else min(least, rows)
            yield chunk

    def _join(self, pieces: list, rows: int, trace) -> np.ndarray:
        """A ``(batch_rows, n_features)`` array of the trace's giving with
        ``pieces`` (``rows`` rows in all) written into its head, in the
        dtype ``np.concatenate`` would give them; rows past ``rows`` are
        the caller's to fill. A ``ColumnBlocks`` piece is joined as it is
        written."""
        dtype = np.result_type(*(piece.dtype for piece in pieces))
        batch = trace.staging((self.batch_rows, self.n_features), dtype)
        if not any(isinstance(piece, ColumnBlocks) for piece in pieces):
            np.concatenate(pieces, axis=0, out=batch[:rows])
            return batch
        start = 0
        for piece in pieces:
            out = batch[start:start + piece.shape[0]]
            if isinstance(piece, ColumnBlocks):
                piece.write_into(out)
            else:
                out[...] = piece
            start += piece.shape[0]
        return batch

    def _copied(self, pieces: list, trace) -> np.ndarray:
        """A full batch assembled from ``pieces`` by one host copy, counted
        and inside the trace's ``copy`` stage."""
        with trace.next_stage("copy"):
            batch = self._join(pieces, self.batch_rows, trace)
        trace.counters["batches_copied"] += 1
        trace.counters["bytes_reblocked"] += batch.nbytes
        return batch

    def batches(self) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """Yield fixed-shape ``(batch, mask)`` pairs; mask None = all valid.

        ``self.trace`` (an ``ops.streaming.IngestTrace``, where a streamed
        fit has set one) is told what the walk does: ``next_stage("read")``
        wraps the reading of each chunk, ``next_stage("copy")`` each host
        copy of re-blocking, and its ``counters`` get ``SOURCE_COUNTERS``'
        keys counted. A copied batch is written into the array
        ``trace.staging(shape, dtype)`` gives: a new one in a walk nobody
        traces; a streamed fit lends one whose pages are already there, and
        lends it again only when the put that read it has landed — so a
        batch of such a walk is the consumer's until it asks for the next.

        Every FULLY-consumed pass must see the same number of rows as the
        first one — a "re-iterable" factory that actually hands back a
        shared, partially-exhausted underlying iterator (one the identity
        check in ``__init__`` cannot see, e.g. ``lambda: map(f, shared_gen)``)
        would otherwise silently zero out second-pass accumulations."""
        trace = self.trace or _Untraced()
        counters = trace.counters
        b, n = self.batch_rows, self.n_features
        carry: list = []
        carry_rows = 0
        pass_rows = 0
        for chunk in self._chunks(trace):
            if not chunk.shape[0]:
                continue  # nothing to add, and maybe no width to check
            pass_rows += chunk.shape[0]
            if chunk.shape[1] != n:
                raise ValueError(
                    f"chunk has {chunk.shape[1]} features, expected {n}"
                )
            start = 0
            # Fill the carry buffer first, then emit whole buckets directly
            # from the chunk (no copy for aligned middles of big chunks).
            if carry_rows:
                need = b - carry_rows
                take = min(need, chunk.shape[0])
                carry.append(chunk[:take])
                carry_rows += take
                start = take
                if carry_rows == b:
                    yield self._copied(carry, trace), None
                    carry, carry_rows = [], 0
            while chunk.shape[0] - start >= b:
                # a slice of a whole chunk leaves the rows where they are;
                # one of column blocks is joined into a batch here
                piece = chunk[start:start + b]
                if isinstance(piece, ColumnBlocks):
                    piece = self._copied([piece], trace)
                else:
                    counters["batches_viewed"] += 1
                yield piece, None
                start += b
            if start < chunk.shape[0]:
                carry.append(chunk[start:])
                carry_rows += chunk.shape[0] - start
        if carry_rows:
            # the fill stage flushes exactly at b, so any remainder here is
            # strictly short: pad + mask
            with trace.next_stage("copy"):
                padded = self._join(carry, carry_rows, trace)
                padded[carry_rows:] = 0
                mask = np.zeros((b,), dtype=bool)
                mask[:carry_rows] = True
            counters["batches_copied"] += 1
            # the tail's rows, written once: its pieces go straight into the
            # padded batch
            counters["bytes_reblocked"] += carry_rows * n * padded.itemsize
            yield padded, mask
        if self._first_pass_rows is None:
            self._first_pass_rows = pass_rows
        elif pass_rows != self._first_pass_rows:
            raise RuntimeError(
                f"streaming pass saw {pass_rows} rows but the first pass saw "
                f"{self._first_pass_rows}; the source factory must return a "
                f"FRESH iterator over the same data on every call"
            )


def streamed_reduce(source, reducer, initial=None):
    """Fold valid rows of a streamed source through ``reducer(acc, rows)``
    — the one masked-iteration loop the host-streamed scaler fits share.
    ``rows`` arrives as float64 with padding removed; empty batches are
    skipped. Raises when the source held no rows at all."""
    import numpy as np

    acc = initial
    seen = False
    for batch, mask in source.batches():
        rows = np.asarray(
            batch if mask is None else batch[mask], dtype=np.float64
        )
        if rows.shape[0] == 0:
            continue
        acc = reducer(acc, rows)
        seen = True
    if not seen:
        raise ValueError("fit requires at least one row")
    return acc
