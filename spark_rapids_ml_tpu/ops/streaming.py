"""Streaming (chunked) PCA fit: bounded HBM for unbounded rows.

The reference streams per-partition chunks through the GPU (one JNI GEMM
per partition, ``RapidsRowMatrix.scala:168-202``). The TPU-native analogue:
an on-device sufficient-statistics accumulator ``(Σxxᵀ, Σx, n)`` updated by
a jitted, buffer-donating step per batch — HBM usage is one batch + one
n×n Gram regardless of total rows, and batches stream through while the
MXU stays busy.

This is also the host data-loader contract: feed fixed-shape batches
(pad + mask the tail — no recompilation), call ``update``, then
``finalize(k)``.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import threading
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.data.batches import SOURCE_COUNTERS
from spark_rapids_ml_tpu.obs import spans as obs_spans
from spark_rapids_ml_tpu.obs.memory import device_memory_stats
from spark_rapids_ml_tpu.obs.report import current_fit
from spark_rapids_ml_tpu.obs.xprof import tracked_jit
from spark_rapids_ml_tpu.ops.covariance import covariance_from_stats, partial_gram_stats
from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance
from spark_rapids_ml_tpu.ops.pca_kernel import PCAFitResult
from spark_rapids_ml_tpu.utils.timing import PhaseTimer
from spark_rapids_ml_tpu.utils.tracing import TraceColor, TraceRange


class GramStats(NamedTuple):
    """Device-resident accumulator: Gram (n×n), column sum (n,), row count."""

    gram: jnp.ndarray
    col_sum: jnp.ndarray
    count: jnp.ndarray


def init_stats(n_features: int, dtype=jnp.float32, device=None) -> GramStats:
    # allocated ON ``device`` (None = the default device), not on device 0
    # and copied over
    zeros = partial(jnp.zeros, dtype=dtype, device=device)
    return GramStats(
        gram=zeros((n_features, n_features)),
        col_sum=zeros((n_features,)),
        # int32, not the compute dtype: f32 counts lose exactness past 2^24
        # rows (see ops.covariance.row_count)
        count=jnp.zeros((), dtype=jnp.int32, device=device),
    )


@partial(tracked_jit, donate_argnums=(0,), static_argnames=("precision",))
def update_stats(
    stats: GramStats, batch: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
    precision: Optional[str] = None,
) -> GramStats:
    """Accumulate one batch. ``stats`` buffers are DONATED — XLA updates the
    Gram in place (no n×n copy per batch). ``precision`` is static (part
    of the jit key) so switching Gram precision retraces."""
    g, s, cnt = partial_gram_stats(batch.astype(stats.gram.dtype), mask,
                                   precision=precision)
    return GramStats(stats.gram + g, stats.col_sum + s, stats.count + cnt)


@partial(
    tracked_jit, static_argnames=("k", "mean_centering", "flip_signs", "solver")
)
def finalize_stats(
    stats: GramStats,
    k: int,
    mean_centering: bool = True,
    flip_signs: bool = True,
    solver: str = "eigh",
) -> PCAFitResult:
    cov = covariance_from_stats(
        stats.gram, stats.col_sum, stats.count, mean_centering=mean_centering
    )
    if mean_centering:
        mean = stats.col_sum / stats.count
    else:
        mean = jnp.zeros_like(stats.col_sum)
    # 'auto' resolves statically (this function is jitted, so the residual
    # gate cannot run here — eager callers wanting the gate use
    # ops.eigh.pca_from_covariance_gated directly, as bench.py and the
    # PCA fit's models.pca.solve_on_chip do)
    components, evr = pca_from_covariance(
        cov, k, flip_signs=flip_signs, solver=solver
    )
    return PCAFitResult(components, evr, mean)


@partial(tracked_jit, donate_argnums=(0,), static_argnames=("precision",))
def _update_stats_fused_blocked(stats: GramStats, batch: jnp.ndarray,
                                precision: Optional[str] = None
                                ) -> GramStats:
    """``update_stats`` with the Gram computed by the Pallas symmetric
    folded-grid kernel (``ops.pallas_gram``) instead of ``lax.dot_general``.
    Takes what ``accumulate_path`` calls ``"pallas"``: tile-aligned batches
    and no mask."""
    from spark_rapids_ml_tpu.ops.pallas_gram import fused_centered_gram

    b = batch.astype(stats.gram.dtype)
    zero_mean = jnp.zeros((b.shape[1],), dtype=b.dtype)
    ones = jnp.ones((b.shape[0],), dtype=b.dtype)
    g = fused_centered_gram(b, zero_mean, ones, precision=precision)
    s = jnp.sum(b, axis=0)
    cnt = jnp.asarray(b.shape[0], dtype=jnp.int32)
    return GramStats(stats.gram + g, stats.col_sum + s, stats.count + cnt)


def _gram_platform(gram_acc) -> str:
    """Platform of the accumulator's device (seam for dispatch tests)."""
    return next(iter(gram_acc.devices())).platform


def accumulate_path(gram_acc, batch, mask) -> str:
    """``"pallas"`` or ``"xla"``: the Gram kernel ``update_stats_auto`` /
    ``update_centered_gram_auto`` pick for this (acc, batch, mask) — the one
    place that chooses. The Pallas kernel visits only the upper block tiles,
    half the MXU work and half the block fetches of a ``dot_general``, so it
    runs wherever it can run as it is; nothing here pads a batch to make it
    fit. The benchmark holds a cell on each side (4096 wide → Pallas, 784 →
    XLA)."""
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_N, _BLOCK_R

    rows, n = batch.shape
    pallas = (
        mask is None  # the fused steps feed the kernel a rowmul of ones
        and gram_acc.dtype == jnp.float32
        and rows % _BLOCK_R == 0
        # an even number of feature tiles: an odd one cannot fold
        and n % (2 * _BLOCK_N) == 0
        # a traced accumulator has no device to ask
        and not isinstance(gram_acc, jax.core.Tracer)
        and _gram_platform(gram_acc) == "tpu"  # the kernel is Mosaic-only
    )
    return "pallas" if pallas else "xla"


def update_stats_auto(
    stats: GramStats, batch: jnp.ndarray, mask: Optional[jnp.ndarray] = None,
    precision: Optional[str] = None,
) -> GramStats:
    """The production accumulate step, by the kernel ``accumulate_path``
    names."""
    if accumulate_path(stats.gram, batch, mask) == "pallas":
        return _update_stats_fused_blocked(stats, batch, precision=precision)
    return update_stats(stats, batch, mask, precision=precision)


class StreamingPCA:
    """Convenience wrapper: ``StreamingPCA(n).partial_fit(b)...finalize(k)``."""

    def __init__(self, n_features: int, dtype=jnp.float32, device=None):
        self._stats = init_stats(n_features, dtype=dtype, device=device)

    def partial_fit(self, batch, mask=None) -> "StreamingPCA":
        self._stats = update_stats_auto(self._stats, batch, mask)
        return self

    @property
    def rows_seen(self) -> float:
        return float(self._stats.count)

    def finalize(
        self, k: int, mean_centering: bool = True, solver: str = "eigh"
    ) -> PCAFitResult:
        return jax.block_until_ready(
            finalize_stats(
                self._stats, k, mean_centering=mean_centering, solver=solver
            )
        )


# -- two-pass streaming (exact reference semantics, out-of-core) -----------
#
# The one-pass (Σxxᵀ, Σx, n) accumulator above loses accuracy to f32
# cancellation in G − n·μμᵀ when |μ| ≫ σ. A re-iterable source affords the
# reference's own schedule out-of-core: pass 1 streams (Σx, n) → μ, pass 2
# streams the CENTERED Gram — numerically the two-pass fit kernel. The loop
# itself needs little HBM (``IngestTrace.put`` holds a chip to
# ``PUTS_IN_FLIGHT`` batches in flight, whatever the stream's length), so
# pass 1 keeps its device batches for pass 2 while ``keep_budget_bytes`` has
# room: rows that fit on the chip cross once.
#
# Pass 2 is the fallback, not the rule. For any fixed c,
# Σ(x−μ)(x−μ)ᵀ = Σ(x−c)(x−c)ᵀ − n·(μ−c)(μ−c)ᵀ exactly, so pass 1 also runs
# the centred-Gram step of each batch as it lands, about c = the mean of the
# chip's FIRST batch, and ``recentre_gram`` moves the sum to μ once μ is
# known: the Gram steps run under the crossing instead of behind it, with
# the programs pass 2 runs. The shift costs float32 digits only where c is
# far from μ against the columns' spread; ``recentre_gram`` measures that
# on the rows themselves (ρ) and the fit runs pass 2 over the kept batches,
# as it always did, when ρ passes ``SHIFT_RATIO_MAX``.

class MeanStats(NamedTuple):
    col_sum: jnp.ndarray
    count: jnp.ndarray


@partial(tracked_jit, donate_argnums=(0,))
def update_mean_stats(
    stats: MeanStats, batch: jnp.ndarray, mask: Optional[jnp.ndarray] = None
) -> MeanStats:
    from spark_rapids_ml_tpu.ops.covariance import _masked, row_count

    b = batch.astype(stats.col_sum.dtype)
    return MeanStats(
        stats.col_sum + jnp.sum(_masked(b, mask), axis=0),
        stats.count + row_count(b, mask),
    )


@partial(tracked_jit, donate_argnums=(0,), static_argnames=("precision",))
def update_centered_gram(
    gram_acc: jnp.ndarray,
    batch: jnp.ndarray,
    mean: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    precision: Optional[str] = None,
) -> jnp.ndarray:
    from spark_rapids_ml_tpu.ops.covariance import _masked, gram

    b = batch.astype(gram_acc.dtype) - mean[None, :]
    return gram_acc + gram(_masked(b, mask), precision=precision)


@partial(tracked_jit, donate_argnums=(0,), static_argnames=("precision",))
def _update_centered_gram_fused_blocked(gram_acc, batch, mean, precision=None):
    from spark_rapids_ml_tpu.ops.pallas_gram import fused_centered_gram

    b = batch.astype(gram_acc.dtype)
    ones = jnp.ones((b.shape[0],), dtype=b.dtype)
    return gram_acc + fused_centered_gram(b, mean.astype(b.dtype), ones,
                                          precision=precision)


def update_centered_gram_auto(gram_acc, batch, mean, mask=None,
                              precision=None):
    """Centered-Gram accumulate by the kernel ``accumulate_path`` names: the
    Pallas kernel centers in VMEM (no (X−μ) materialization at all)."""
    if accumulate_path(gram_acc, batch, mask) == "pallas":
        return _update_centered_gram_fused_blocked(gram_acc, batch, mean,
                                                   precision=precision)
    return update_centered_gram(gram_acc, batch, mean, mask,
                                precision=precision)


# The largest ρ a shifted Gram is accepted with, ρ = max_j of
#
#     n·|d_j|·(|d_j| + |μ_j| / 32) / G_jj,    d = μ − c,  G = Σ(x−c)(x−c)ᵀ.
#
# Its first term is the share of a column's shifted sum of squares that the
# re-centring takes away again: the centred diagonal is G_jj·(1 − that), so
# the shifted sum's own float32 rounding weighs 1/(1 − ρ) in the result — at
# ρ ≤ 1/64 at most 1.6 % more than pass 2's rounding of the same sum, nothing
# beside the margin the benchmark's ``ritz_gap`` keeps (sound 4.5e-6 on a
# limit of 2e-5). Its second term is the float32 mean's own rounding, which
# pass 2 feels at second order and the correction n·d dᵀ at first: μ is off
# by a few ulp of |μ_j| (κ ≈ 6 by the benchmark's ``mean_gap``), which moves
# the centred diagonal by 2κε·n|d_j μ_j| — with the weight 1/32 at most
# κε ≈ 4e-7 of G_jj at ρ = 1/64, a tenth of the Gram's own rounding.
# Rows in any order that is not sorted by value read d_j² ≈ σ_j²/batchRows
# (i.i.d.: (1 − batchRows/n)·χ²₁ a column), so ρ ≈ 1/batchRows unless |μ| ≫ σ
# by more than about √batchRows / 8; a frame sorted by a feature, a drifting
# stream or a small batch of N(100, 1) reads more and gets pass 2, the case
# it exists for. Not a Param: nothing a caller knows says more than the
# rows do.
SHIFT_RATIO_MAX = 1.0 / 64
_MEAN_LEAK_WEIGHT = 1.0 / 32


@partial(tracked_jit, donate_argnums=(0,))
def recentre_gram(gram_acc, col_sum, count, shift, mean):
    """(Σ(x−μ)(x−μ)ᵀ, ρ) of one chip's rows from their shifted Gram
    Σ(x−c)(x−c)ᵀ (donated: re-centred in place), their (Σx, n), the shift c
    and the mean μ the Gram is wanted about — the chip's own rows' on one
    chip, all the chips' rows' on several: G − s dᵀ − d sᵀ + n d dᵀ with
    s = Σ(x−c) = Σx − n c and d = μ − c (s = n d on one chip). ρ is what
    ``SHIFT_RATIO_MAX`` bounds; 0/0, a column constant at c, reads 0, and a
    NaN stays one (``ρ ≤ SHIFT_RATIO_MAX`` is then false)."""
    n = count.astype(gram_acc.dtype)
    d = mean - shift
    s = col_sum - n * shift
    moved = n * jnp.abs(d) * (jnp.abs(d) + _MEAN_LEAK_WEIGHT * jnp.abs(mean))
    ratio = jnp.max(jnp.where(moved == 0, 0, moved / jnp.diagonal(gram_acc)))
    centred = (gram_acc - jnp.outer(s, d) - jnp.outer(d, s)
               + n * jnp.outer(d, d))
    return centred, ratio


# -- spans, sub-phases and counters of the streamed fit ---------------------
#
# Every stage of ``stream_covariance`` is a ``TraceRange`` (a host span in
# the profiler's trace and in the ``obs.spans`` ring), seconds in the fit's
# ``PhaseTimer`` and counters on ``current_fit()``. The names are the
# benchmark's yardstick (``benchmarks/work/spans.py`` mirrors the stream's,
# ``benchmarks/work/collective.py`` the collectives'); the spans wrap what
# the loop does and add no sync, host read or program.

SPAN_PASS_MEAN = "stream:pass/mean"
SPAN_PASS_GRAM = "stream:pass/gram"
SPAN_PASS_STATS = "stream:pass/stats"
SPAN_NEXT = "stream:next"
SPAN_PUT = "stream:put"
SPAN_ACCUMULATE = {"mean": "stream:accumulate/mean",
                   "pallas": "stream:accumulate/pallas",
                   "xla": "stream:accumulate/xla"}
SPAN_SYNC_COUNT = "stream:sync/count"
SPAN_SYNC_COV = "stream:sync/cov"
STREAM_SPANS = (SPAN_PASS_MEAN, SPAN_PASS_GRAM, SPAN_PASS_STATS, SPAN_NEXT,
                SPAN_PUT, *SPAN_ACCUMULATE.values(), SPAN_SYNC_COUNT,
                SPAN_SYNC_COV)
# inside ``stream:next``, emitted by the source as it is walked
# (``data.batches.BatchSource.batches``): the pull of the next chunk from
# the dataset and its reading into a 2-D array (``data.arrow`` for a
# columnar chunk), and each host copy of re-blocking (a batch assembled from
# several chunks, a padded tail). Not in ``STREAM_SPANS``: the benchmark
# lists them apart (``benchmarks/work/reblock.py``), so idle seconds under
# them count as ``stream:next``'s.
SPAN_NEXT_PART = {"read": "stream:next/read", "copy": "stream:next/copy"}
# only a fit over several chips emits these: each wraps the dispatch of one
# all-reduce (the chips' parts handed to the mesh program) and nothing else
SPAN_COLLECTIVE = {"mean": "stream:collective/mean",
                   "gram": "stream:collective/gram"}
# not on the main thread: one span a put, ``stream:landing/<device id>``, on
# the line of the device's landing watcher (``_Watcher``), from the moment
# the put is the chip's oldest outstanding to its landing. Not in
# ``STREAM_SPANS`` either: ``benchmarks/work/crossing.py`` lists the name,
# so the idle readers that keep only the listed spans read what they read.
SPAN_LANDING = "stream:landing"

PHASE_NEXT = "covariance/next"
PHASE_NEXT_PART = {"read": "covariance/next/read",
                   "copy": "covariance/next/copy"}
PHASE_PUT = "covariance/put"
PHASE_DISPATCH = "covariance/dispatch"
PHASE_SYNC = "covariance/sync"
PHASE_COLLECTIVE = "covariance/collective"
# the seconds during which a put of the fit's fullest chip was outstanding
# (that chip's ``crossing_seconds``). They OVERLAP the main thread's phases,
# as ``covariance/put`` nests in ``covariance``: the link works while the
# main thread reads, copies, dispatches and waits.
PHASE_CROSSING = "covariance/crossing"
# a chip's own, in ``per_chip[]``: its puts seen to land, the seconds one
# was outstanding, and from the fit's first put's start to the chip's last
# landing (where the chips meet: the collectives wait for the latest)
LANDING_COUNTERS = ("landings", "crossing_seconds", "last_landing_seconds")


def _boundary(span: str) -> str:
    """``hbm_bytes_in_use`` key of a span: its name without ``stream:``."""
    return span.partition(":")[2]


def keep_budget_bytes(device, reserve_nbytes: int) -> int:
    """Bytes of pass-1 device batches a fit may keep on ``device`` for its
    later walks: what ``memory_stats()`` reports free now, less
    ``reserve_nbytes``, what the consumer's loop needs when it keeps
    nothing (a Gram loop's: ``gram_reserve_bytes``; a Lloyd step's:
    ``ops.kmeans_kernel.step_reserve_bytes``). 0 where the backend reports
    no ``memory_stats()`` (the CPU): nothing is kept. Tests patch this
    function to stand in for the chip."""
    stats = device_memory_stats(device)
    if stats is None or not {"bytes_limit", "bytes_in_use"} <= set(stats):
        return 0
    free = int(stats["bytes_limit"]) - int(stats["bytes_in_use"])
    return max(0, free - reserve_nbytes)


def gram_reserve_bytes(batch_nbytes: int, gram_nbytes: int) -> int:
    """Device bytes the Gram loop needs beside the batches a fit keeps:
    three batches (one landing, one being summed, the XLA Gram's centred
    copy of one) and three n×n (the accumulator, a step's product before it
    is added, the normalised covariance or, on several chips, the
    all-reduced Gram)."""
    return 3 * batch_nbytes + 3 * gram_nbytes


# Puts a chip may have in flight: the batch crossing and the one being
# re-tiled behind it. The link is FIFO, and a program whose batch has landed
# still starts only after every transfer queued by then has landed too
# (recorded v5e traces, ``PERF.md`` §5): with all of a fit's puts issued up
# front every accumulate step ran behind the LAST landing; with two, step i
# runs at landing i+1, under crossing i+2. One batch's re-tiling (0.06 s at
# 4096) fits inside one crossing (0.15 s), so two keep the link full.
# They count the slices that cross (``PUT_BYTES_MAX``), not host batches.
PUTS_IN_FLIGHT = 2

# The most bytes one put hands the link. A host batch is the source's unit
# of re-blocking and host copy; what crosses is a row slice of it. The
# runtime re-tiles a put before it joins the link's queue, with the link
# idle ahead of a fit's first put, and a step runs only once every
# transfer queued before its batch's landing has landed, so the last
# ``PUTS_IN_FLIGHT`` puts' steps run after the last landing: both the head
# and the tail of a crossing scale with the rows of one put, not with
# ``batchRows`` (v5e traces, ``PERF.md`` §5). 1 GiB is two slices of the
# 4096-wide cells' 2 GiB batches; the estimators' default 128 MiB batches
# cross whole. Not a Param: the cut follows the batch's bytes.
PUT_BYTES_MAX = 1 << 30


def put_slices(rows: int, nbytes: int) -> int:
    """Into how many equal row slices a host batch of ``rows`` rows and
    ``nbytes`` bytes is cut for the link: ⌈nbytes ÷ ``PUT_BYTES_MAX``⌉, or
    1 (the batch whole) where that many do not cut its rows evenly, or
    would cut a batch whose rows are a multiple of the Pallas row block
    into slices that are not (``accumulate_path`` would then leave the
    kernel for the XLA Gram)."""
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_R

    pieces = -(-nbytes // PUT_BYTES_MAX)
    if pieces <= 1 or rows % pieces:
        return 1
    if rows % _BLOCK_R == 0 and (rows // pieces) % _BLOCK_R:
        return 1
    return pieces


def wait_for_landing(x_dev) -> None:
    """Block until the device batch ``device_put`` returned is on its chip
    (``block_until_ready`` is a fence on the TPU runtime; it returns at once
    on the CPU). Tests patch this function to stand in for the chip."""
    jax.block_until_ready(x_dev)


def landing_of(x_dev) -> None:
    """``wait_for_landing`` for the landing watchers: the same fence through
    a seam of its own, so that a test (or a reader of a log) can tell the
    main thread's waits — the put window's — from a watcher's, which no
    step of the fit waits for. Tests patch this function to stand in for
    the chip."""
    jax.block_until_ready(x_dev)


def put_copies(device) -> bool:
    """Whether ``jax.device_put`` to ``device`` copies the host array into
    memory of the device's own, so that nothing reads the host array once
    the put has landed. A chip does. The CPU backend does not: a host array
    that is 64-byte aligned *becomes* the device array (jax 0.9.0:
    zero-copy, whatever ``may_alias`` says), and a later write to it changes
    the batch a queued step has yet to read — a staging buffer put there is
    the device array's from then on, and never lent again. Tests patch this
    function (and make ``device_put`` copy) to stand in for the chip."""
    return device.platform != "cpu"


class StagingPool:
    """Host arrays for re-blocking whose pages are already there.

    A device batch assembled from several chunks is a host copy into an
    array of ``batchRows`` × n (2 GiB in the 4096-wide cells). glibc hands
    a freed block that large back to the kernel, so an array made for every
    batch pays its first touches every time: 2.2–2.3 s of a 2 GiB copy that
    takes 0.11 s into touched pages (v5e host, no transparent huge pages;
    ``PERF.md`` §6 PR 34), and the runtime's ``device_put`` call takes
    0.17 s for a host array it has not been handed before against 0.5 ms
    for one it has (§6 PR 35). The pool keeps the arrays between batches *and
    between fits*: a fit of four batches that made its own would still
    touch three of four.

    It holds free arrays of ONE (shape, dtype) — the last asked for; asking
    for another lets the old ones go — and only what ``IngestTrace`` hands
    back, which it does when the put that read an array has landed. Made
    empty: nothing is allocated until a streamed fit copies a batch. What a
    process then keeps between fits is up to (``PUTS_IN_FLIGHT`` + 1) × the
    fit's chips batches of host memory (6 GiB on one chip at
    131,072 × 4096 float32)."""

    def __init__(self):
        self._lock = threading.Lock()  # fits may run on several threads
        self._key = None  # (shape, dtype) of the arrays in ``_free``
        self._free: list = []

    def lend(self, shape, dtype, most: int):
        """(an array of ``shape`` and ``dtype``, whether the pool had it):
        the borrower's until it hands it to ``take_back`` or drops it. The
        pool keeps at most ``most`` others."""
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            if key != self._key:
                self._key, self._free = key, []
            del self._free[most:]
            if self._free:
                return self._free.pop(), True
        return np.empty(*key), False

    def take_back(self, buffer: np.ndarray, most: int) -> None:
        """``buffer`` is read by nobody any more. Kept if it is of the shape
        last asked for and fewer than ``most`` are free."""
        with self._lock:
            if (buffer.shape, buffer.dtype) == self._key \
                    and len(self._free) < most:
                self._free.append(buffer)

    def free(self) -> list:
        """The arrays nobody has borrowed (a copy of the list)."""
        with self._lock:
            return list(self._free)


# the process's one pool: staging buffers have to outlive a fit to be worth
# anything (see ``StagingPool``)
STAGING = StagingPool()


class _Watcher:
    """A device's landing watcher, one for the process: a FIFO of the puts
    to that chip and a daemon thread that takes them oldest first and, for
    each, opens the span ``stream:landing/<device id>``, blocks until the
    device batch is on the chip (``landing_of``) and closes the span. A span
    therefore runs from max(the put's ``device_put`` returned, the chip's
    previous landing) — the thread's wake-up later: 0.2 ms on the v5e's
    host — to the landing, and the union of a chip's spans is the time
    during which a put of that chip was outstanding: the runtime's
    re-tiling of the batch, its wait in the link's queue and its crossing.
    The span is a ``TraceRange`` on the watcher's own host line (the device
    trace's clock, like every span of the fit); the same seconds go into
    the ``obs.spans`` ring under the fit's trace id — with the chip, the
    put's index in the fit and its bytes as args — and into the counters
    the put came with (its chip's ``LANDING_COUNTERS``).

    The watcher adds no wait to a fit and keeps no batch past its landing:
    the main thread hands a put over and goes on, and the thread drops its
    reference the moment ``landing_of`` returns. A fit that dies leaves its
    puts to land into counters nobody reads. Kept for the process, not made
    per fit: a hand-fed stream that is dropped unfinished would leave a
    thread of its own waiting for ever, and this one costs a fit no thread
    start. ``fence`` is how a fit knows the watcher is done with it."""

    def __init__(self, device_id: int):
        self.span = f"{SPAN_LANDING}/{device_id}"
        self.device_id = device_id
        self.fifo: queue.SimpleQueue = queue.SimpleQueue()
        threading.Thread(target=self._run, name=self.span,
                         daemon=True).start()

    def fence(self) -> threading.Event:
        """Set once every put handed over before it has been seen to land
        and is counted."""
        seen = threading.Event()
        self.fifo.put(seen)
        return seen

    def _run(self) -> None:
        while True:
            put = self.fifo.get()
            if isinstance(put, threading.Event):
                put.set()
                continue
            x_dev, nbytes, index, since, trace_id, counters = put
            del put
            with TraceRange(self.span, TraceColor.ORANGE,
                            record=False) as span:
                try:
                    landing_of(x_dev)
                except Exception:  # noqa: BLE001 - the fit's own wait raises
                    pass
            del x_dev  # the device batch: not kept past its landing
            now = time.perf_counter()
            obs_spans.record_event(
                self.span, now - span.elapsed, now, trace_id=trace_id,
                color=TraceColor.ORANGE.name, chip=self.device_id, put=index,
                bytes=nbytes)
            counters["landings"] += 1
            counters["crossing_seconds"] += span.elapsed
            counters["last_landing_seconds"] = now - since


_WATCHERS: dict = {}  # device id → its ``_Watcher``, made at its first put
_WATCHERS_LOCK = threading.Lock()


def watcher_of(device) -> _Watcher:
    with _WATCHERS_LOCK:
        if device.id not in _WATCHERS:
            _WATCHERS[device.id] = _Watcher(device.id)
        return _WATCHERS[device.id]


class _Chip:
    """One chip's share of a streamed fit: where its batches go, what of the
    keep budget is left there, the puts it has not seen land, its device's
    landing watcher (from its first put on) and its own counters."""

    def __init__(self, device):
        self.device = device  # None = JAX's default device, uncommitted
        self.keep_room = 0  # bytes of the chip's budget not taken yet
        # (device batch, the staging buffer its slice was put from or None)
        # of the puts here not yet waited for, oldest first: at most
        # ``PUTS_IN_FLIGHT``
        self.in_flight = collections.deque()
        self.counters = {
            "device": str(self.stats_device()), "rows": 0, "rows_put": 0,
            "bytes_put": 0, "batches_kept": 0, "bytes_kept": 0,
            "keep_budget_bytes": 0, "hbm_bytes_in_use": {},
            "puts_in_flight_max": 0, "put_waits": 0, "put_wait_seconds": 0.0,
            "landings": 0, "crossing_seconds": 0.0,
            "last_landing_seconds": 0.0,
        }
        self.watcher: Optional[_Watcher] = None

    def stats_device(self):
        return self.device or jax.local_devices()[0]


class IngestTrace:
    """What one streamed fit tells about its ingest: the spans above, the
    ``covariance/*`` seconds summed in ``timer``, and the counters that
    reach ``fit_report_.extra["ingest"]``. It cuts each host batch the
    source yields into the row slices that cross the link (``batches``,
    ``put_slices``: a batch over ``PUT_BYTES_MAX`` crosses in equal
    slices, views each with its slice of the mask) and from there on a
    slice is what every consumer calls a batch: it deals them to the fit's
    chips (``device``: one, or a sequence of them) whole and in turn, and
    holds the device batches a two-pass fit keeps from pass 1 for pass 2
    (``keep`` / ``replay``), each chip under its own budget. A chip has at
    most ``PUTS_IN_FLIGHT`` (two) puts in flight: its third waits for its
    first to land, because a landed batch's step starts only after the
    transfers queued behind it (v5e traces: behind the fit's last landing
    when all puts went out at once).
    It also lends the source the arrays it assembles copied batches into
    (``staging``, out of the process's ``STAGING`` pool) and alone says when
    one is free again: when every slice of it is seen to land —
    ``_await_window``'s wait, or ``all_landed`` for the window's last — or
    was never put (passed over, or cast into a new array by ``put``),
    never sooner and never on a guess; a fit that dies drops what it has
    not seen land. With a copy of batch *i* + 2 assembled while puts *i*
    and *i* + 1 are in flight, a chip cycles ``PUTS_IN_FLIGHT`` + 1.
    Every put is also handed to its device's landing watcher (``_Watcher``),
    which sees it land from a thread of its own — the span
    ``stream:landing/<device id>`` and the chip's ``LANDING_COUNTERS`` — and
    takes no part in any of the above: the main thread waits where it
    waited."""

    def __init__(self, timer: Optional[PhaseTimer] = None, device=None):
        self.timer = timer if timer is not None else PhaseTimer()
        for phase in (*PHASE_NEXT_PART.values(), PHASE_CROSSING):
            self.timer.add(phase, 0.0)  # there, at 0, in a fit with none
        devices = device if isinstance(device, (list, tuple)) else (device,)
        self.chips = [_Chip(d) for d in devices]
        self.turn = 0  # batches dealt in this pass: the next goes to
        #                chip ``turn % len(chips)``
        self.pass_rows = 0  # valid rows of the current pass, put or kept
        self.put_rows = 0  # valid rows of the batch last put
        self.itemsize = 0
        # (turn, chip index, valid rows, x_dev, m_dev) of pass 1, in order
        self.kept = collections.deque()
        # staging buffers: the one lent to the source and not yielded yet;
        # id → [buffer, its slices not yet seen landed or passed over] of
        # those yielded; (the slice just yielded, its buffer) until it is
        # put or passed over; those whose puts were still in flight at
        # ``release``, one entry a slice
        self._assembling: Optional[np.ndarray] = None
        self._unsettled: dict = {}
        self._lent: Optional[tuple] = None
        self._unlanded: list = []
        self._staging_most = (PUTS_IN_FLIGHT + 1) * len(self.chips)
        self._first_put: Optional[float] = None  # when it started
        self.counters = {
            # ``batches``: puts, i.e. slices; ``host_batches``: the batches
            # the source yielded in the fit's walks
            "passes": 0, "batches": 0, "host_batches": 0, "rows_put": 0,
            "bytes_put": 0,
            # counted by the source as it is walked (``staging_*``: by
            # ``staging``, which the source asks)
            **SOURCE_COUNTERS,
            "batches_kept": 0, "bytes_kept": 0, "keep_budget_bytes": 0,
            "accumulate_calls": {"mean": 0, "pallas": 0, "xla": 0},
            "put_seconds_max": 0.0, "sync_seconds_max": 0.0,
            "puts_in_flight_max": 0, "put_waits": 0, "put_wait_seconds": 0.0,
            # the fullest chip's, at ``all_landed`` (``LANDING_COUNTERS``
            # are each chip's own, in ``per_chip``); and from the fit's last
            # landing to that call: the device work and reads the link
            # left behind it
            "crossing_seconds": 0.0, "landing_tail_seconds": 0.0,
            "hbm_bytes_in_use": {},
            "chips": len(self.chips), "collective_bytes": {},
            "per_chip": [chip.counters for chip in self.chips],
        }
        # noted now, filled as the fit goes: a fit that dies keeps its count
        current_fit().note(ingest=self.counters)

    @property
    def devices(self) -> tuple:
        return tuple(chip.device for chip in self.chips)

    @contextlib.contextmanager
    def stage(self, span: str, phase: str, slowest: Optional[str] = None):
        t0 = time.perf_counter()
        with TraceRange(span, TraceColor.ORANGE):
            yield
        seconds = time.perf_counter() - t0
        self.timer.add(phase, seconds)
        if slowest is not None:
            self.counters[slowest] = max(self.counters[slowest], seconds)

    def hbm(self, boundary: str) -> None:
        """Device bytes in use now on every chip of the fit, kept under
        ``boundary`` (the fit's own key holds the fullest chip's); nothing
        where the backend has no ``memory_stats()`` (the CPU)."""
        for chip in self.chips:
            stats = device_memory_stats(chip.stats_device())
            if stats is not None and "bytes_in_use" in stats:
                in_use = int(stats["bytes_in_use"])
                chip.counters["hbm_bytes_in_use"][boundary] = in_use
                fullest = self.counters["hbm_bytes_in_use"]
                fullest[boundary] = max(fullest.get(boundary, 0), in_use)

    @contextlib.contextmanager
    def walk(self, span: str):
        """One pass over ``source.batches()``."""
        self.counters["passes"] += 1
        self.pass_rows = self.turn = 0
        for chip in self.chips:
            chip.counters["rows"] = 0
        with TraceRange(span, TraceColor.YELLOW):
            yield
        # the pass's last put has returned and its last program is queued
        self.hbm(_boundary(span) + ":end")

    def next_stage(self, part: str):
        """A stage of the source's own inside ``stream:next``: ``"read"``
        or ``"copy"``."""
        return self.stage(SPAN_NEXT_PART[part], PHASE_NEXT_PART[part])

    def staging(self, shape, dtype) -> np.ndarray:
        """The array the source assembles its next copied batch into: one
        the pool had (its pages touched) or, failing that, a new one."""
        buffer, reused = STAGING.lend(shape, dtype, self._staging_most)
        self.counters["staging_reused" if reused else "staging_fresh"] += 1
        self._assembling = buffer
        return buffer

    def _take_back(self, buffer: Optional[np.ndarray]) -> None:
        if buffer is not None:
            STAGING.take_back(buffer, self._staging_most)

    def _free_unput(self) -> None:
        """A buffer lent and never yielded was read by nobody."""
        self._take_back(self._assembling)
        self._assembling = None

    def _settle(self, buffer: Optional[np.ndarray]) -> None:
        """One slice of ``buffer`` is read by nobody any more (seen to land,
        passed over, or cast into a new array): the buffer goes back to the
        pool with its last."""
        entry = None if buffer is None else self._unsettled.get(id(buffer))
        if entry is None:
            return
        entry[1] -= 1
        if not entry[1]:
            del self._unsettled[id(buffer)]
            self._take_back(buffer)

    def batches(self, source):
        """The slices that cross the link, as (rows, mask) pairs: each host
        batch of ``source.batches()`` cut by ``put_slices`` into views, each
        with its slice of the mask (one slice, the batch itself, at or
        under ``PUT_BYTES_MAX``). A slice of a staging buffer that the
        consumer asks past without putting it was read by nobody."""
        # the source reports its reads, copies and counts here
        # (``BatchSource.batches``; a stand-in that does not is left alone)
        source.trace = self
        batches = source.batches()
        while True:
            with self.stage(SPAN_NEXT, PHASE_NEXT):
                item = next(batches, None)
            if item is None:
                return
            batch, mask = item
            self.counters["host_batches"] += 1
            staged, self._assembling = self._assembling, None
            rows = batch.shape[0]
            pieces = put_slices(rows, batch.nbytes)
            if staged is not None:
                self._unsettled[id(staged)] = [staged, pieces]
            step = rows // pieces
            cuts = [(batch, mask)] if pieces == 1 else [
                (batch[s:s + step], None if mask is None else mask[s:s + step])
                for s in range(0, rows, step)]
            for piece, piece_mask in cuts:
                self._lent = None if staged is None else (piece, staged)
                yield piece, piece_mask
                if self._lent is not None:  # not put: passed over
                    self._settle(self._lent[1])
                    self._lent = None

    def _count_rows(self, c: int, valid: int) -> None:
        self.pass_rows += valid
        self.chips[c].counters["rows"] += valid

    def _await_window(self, chip: _Chip) -> None:
        """Before a put that would be ``chip``'s third in flight: wait for
        its oldest to land, and let go of it — its staging buffer, if it
        had one, back to the pool once its last slice has landed."""
        if len(chip.in_flight) < PUTS_IN_FLIGHT:
            return
        t0 = time.perf_counter()
        x_dev, staged = chip.in_flight.popleft()
        wait_for_landing(x_dev)
        self._settle(staged)
        seconds = time.perf_counter() - t0
        for counters in (self.counters, chip.counters):
            counters["put_waits"] += 1
            counters["put_wait_seconds"] += seconds

    def put(self, batch, mask, dtype):
        """The next host batch → the chip whose turn it is, whole and in
        one hop (``jnp.asarray`` would land it on device 0 whatever the
        chip is), once the chip's window has room (the wait is part of the
        put stage). (chip index, device batch, device mask)."""
        c = self.turn % len(self.chips)
        chip = self.chips[c]
        self.turn += 1
        if self._first_put is None:
            self._first_put = time.perf_counter()
        with self.stage(SPAN_PUT, PHASE_PUT, "put_seconds_max"):
            self._await_window(chip)
            x = np.asarray(batch, dtype=dtype)
            x_dev = jax.device_put(x, chip.device)
            m_dev = None if mask is None else jax.device_put(mask, chip.device)
        if chip.watcher is None:
            chip.watcher = watcher_of(chip.stats_device())
        chip.watcher.fifo.put((x_dev, x.nbytes, self.counters["batches"],
                               self._first_put, obs_spans.current_trace_id(),
                               chip.counters))
        staged = None
        if self._lent is not None and self._lent[0] is batch:
            staged, self._lent = self._lent[1], None
            if x is not batch:
                # a cast made ``x`` another array: nobody reads the slice
                self._settle(staged)
                staged = None
            elif not put_copies(chip.stats_device()):
                # the device array's from now on: never lent again
                self._unsettled.pop(id(staged), None)
                staged = None
        chip.in_flight.append((x_dev, staged))
        for counters in (self.counters, chip.counters):
            counters["puts_in_flight_max"] = max(
                counters["puts_in_flight_max"], len(chip.in_flight))
        self.put_rows = x.shape[0] if mask is None else int(mask.sum())
        self._count_rows(c, self.put_rows)
        self.itemsize = x.itemsize
        self.counters["batches"] += 1
        for counters in (self.counters, chip.counters):
            counters["rows_put"] += x.shape[0]  # padding crosses too
            counters["bytes_put"] += x.nbytes
        return c, x_dev, m_dev

    def allow_keep(self, reserve_nbytes: int) -> None:
        """Size each chip's budget of ``keep`` from the chip as it is now,
        less ``reserve_nbytes``: what the consumer's loop needs when it
        keeps nothing (``keep_budget_bytes``)."""
        for chip in self.chips:
            chip.keep_room = keep_budget_bytes(chip.stats_device(),
                                               reserve_nbytes)
            chip.counters["keep_budget_bytes"] = chip.keep_room
        self.counters["keep_budget_bytes"] = sum(
            chip.keep_room for chip in self.chips)

    def keep(self, c: int, x_dev, m_dev) -> bool:
        """Hold the device batch just put on chip ``c`` for the later walks
        if the chip's budget has room for it; whether it did. Only a prefix
        of a chip's batches is kept: the first that finds no room closes
        that chip's budget."""
        chip = self.chips[c]
        if x_dev.nbytes > chip.keep_room:
            chip.keep_room = 0
            return False
        chip.keep_room -= x_dev.nbytes
        self.kept.append((self.turn - 1, c, self.put_rows, x_dev, m_dev))
        for counters in (self.counters, chip.counters):
            counters["batches_kept"] += 1
            counters["bytes_kept"] += x_dev.nbytes
        return True

    def replay(self, source, dtype, drain: bool = True):
        """A later walk's device batches, each with its chip: the kept ones
        from the chips in pass 1's order, then the rest of the source, dealt
        as in pass 1 and put again. ``drain`` (pass 2 of a Gram): each kept
        reference is dropped as it is handed out, so HBM drains as the Gram
        steps run. Otherwise the kept batches stay kept, for a consumer that
        walks the rows once a step (a Lloyd fit). With everything kept the
        source is not walked; otherwise the kept host batches are passed
        over with their rows untouched."""
        kept_turns = {k[0] for k in self.kept}
        everything = len(kept_turns) == self.counters["batches"]  # pass 1's
        kept = self.kept if drain else collections.deque(self.kept)
        while kept:
            _, c, valid, x_dev, m_dev = kept.popleft()
            self._count_rows(c, valid)
            yield c, x_dev, m_dev
        if everything:
            return
        for batch, mask in self.batches(source):
            if self.turn in kept_turns:
                self.turn += 1
            else:
                yield self.put(batch, mask, dtype)

    def release(self) -> None:
        """Let go of every device batch held here (kept for pass 2, or in a
        chip's window): none outlives the walks over it. The staging
        buffers of the window's puts stay out of the pool until
        ``all_landed``; a fit that dies never says so, and they go with
        it. A landing watcher lets go of a batch at its landing, not
        here."""
        self.kept.clear()
        self._free_unput()
        for chip in self.chips:
            self._unlanded += [staged for _, staged in chip.in_flight
                               if staged is not None]
            chip.in_flight.clear()

    def all_landed(self) -> None:
        """The host has read a value that every put of the fit fed (the
        covariance, the solve's result): the buffers ``release`` held back
        are free, and the landing watchers have seen the fit's last
        landing — from here on none works for this fit, and its landing
        counters and ``covariance/crossing`` are final. ``landing_tail_seconds``
        is from the fit's last landing (the latest chip's, on the
        watchers' clock) to this call."""
        called = time.perf_counter()
        for staged in self._unlanded:
            self._settle(staged)
        self._unlanded = []
        for seen in [chip.watcher.fence() for chip in self.chips
                     if chip.watcher is not None]:
            seen.wait()
        fullest = max(chip.counters["crossing_seconds"]
                      for chip in self.chips)
        # (a hand-fed stream that goes on and says so again adds the rest)
        self.timer.add(PHASE_CROSSING,
                       fullest - self.counters["crossing_seconds"])
        self.counters["crossing_seconds"] = fullest
        if self._first_put is not None:
            last = max(chip.counters["last_landing_seconds"]
                       for chip in self.chips)
            self.counters["landing_tail_seconds"] = \
                called - (self._first_put + last)

    def accumulate(self, path: str):
        self.counters["accumulate_calls"][path] += 1
        return self.stage(SPAN_ACCUMULATE[path], PHASE_DISPATCH)

    def shift_verdict(self, ratios: list) -> bool:
        """Whether a two-pass fit's shifted Grams stand, from each chip's ρ
        (``recentre_gram``; the fit's is the largest): noted as
        ``gram_shift`` {``accepted``, ``ratio``}, per chip too."""
        for chip, ratio in zip(self.chips, ratios):
            chip.counters["gram_shift"] = {
                "accepted": ratio <= SHIFT_RATIO_MAX, "ratio": ratio}
        accepted = all(chip.counters["gram_shift"]["accepted"]
                       for chip in self.chips)
        self.counters["gram_shift"] = {"accepted": accepted,
                                       "ratio": max(ratios)}
        return accepted

    def collective(self, kind: str, nbytes: int):
        """The dispatch of one all-reduce whose operand is ``nbytes`` a
        chip."""
        sent = self.counters["collective_bytes"]
        sent[kind] = sent.get(kind, 0) + nbytes
        current_fit().record_collective("all_reduce", nbytes=nbytes)
        return self.stage(SPAN_COLLECTIVE[kind], PHASE_COLLECTIVE)

    def sync(self, span: str):
        """A place where the host blocks on a device value."""
        self.hbm(_boundary(span))
        return self.stage(span, PHASE_SYNC, "sync_seconds_max")

    def set_data(self, n_features: int) -> None:
        """The dataset as the last pass counted it: rows without padding,
        and their bytes once (``bytes_put`` counts every crossing)."""
        current_fit().set_data(
            rows=self.pass_rows, features=n_features,
            nbytes=self.pass_rows * n_features * self.itemsize)


# -- the two collectives of a fit over several chips ------------------------
#
# Each chip sums its own batches with the one-chip programs; the chips meet
# twice (once in a one-pass fit). The mesh programs live in
# ``parallel.mesh`` and are imported only by a fit that has several chips.

def collective_mean(ingest: IngestTrace, mstats: list):
    """Collective (a): the chips' (Σx, n) → (the mean of all rows on every
    chip, the row count on the first)."""
    from spark_rapids_ml_tpu.parallel import mesh as pm

    devices = ingest.devices
    mesh = pm.data_mesh(devices=devices)
    col_sum = mstats[0].col_sum
    with ingest.collective("mean", pm.collective_nbytes(
            (col_sum.shape[0] + 1,), col_sum.dtype)):
        mean, count = pm.all_reduce_mean(
            pm.sharded_over(mesh, [s.col_sum for s in mstats]),
            pm.sharded_over(mesh, [s.count.reshape(1) for s in mstats]),
            mesh=mesh)
    return pm.on_each_chip(mean, devices), pm.on_each_chip(count, devices)[0]


def collective_sum(ingest: IngestTrace, parts: list):
    """Collective (b): the sum over the chips of each chip's tuple of
    accumulators (leaves with a leading axis), on the first chip."""
    from spark_rapids_ml_tpu.parallel import mesh as pm

    devices = ingest.devices
    mesh = pm.data_mesh(devices=devices)
    nbytes = sum(pm.collective_nbytes(a.shape, a.dtype) for a in parts[0])
    with ingest.collective("gram", nbytes):
        total = pm.all_reduce_sum(
            tuple(pm.sharded_over(mesh, leaves) for leaves in zip(*parts)),
            mesh=mesh)
    return tuple(pm.on_each_chip(a, devices[:1])[0] for a in total)


def collective_stats(ingest: IngestTrace, stats: list) -> GramStats:
    """Collective (b) of one-pass accumulators: the chips' (Σxxᵀ, Σx, n)
    summed, on the first chip."""
    gram, col_sum, count = collective_sum(
        ingest, [(s.gram, s.col_sum, s.count.reshape(1)) for s in stats])
    return GramStats(gram, col_sum, count[0])


def stream_gram_stats(
    source,
    dtype=jnp.float32,
    device=None,
    precision: Optional[str] = None,
    ingest: Optional[IngestTrace] = None,
) -> GramStats:
    """Stream a ``data.batches.BatchSource`` once into its raw moments
    (Σxxᵀ, Σx, n): the one-pass walk of ``stream_covariance``, which calls
    this, handed back before any centring. For a caller that is one part of
    a larger sum — an executor task of the Spark front hands its partition's
    moments to a driver that centres once over all partitions. Device
    arrays, on the first of the fit's chips; ``device``, ``ingest`` and
    everything about puts, staging buffers and chips as in
    ``stream_covariance``."""
    if ingest is None:
        ingest = IngestTrace(device=device)
    devices = ingest.devices
    n = source.n_features
    stats = [init_stats(n, dtype=dtype, device=d) for d in devices]
    try:
        with ingest.walk(SPAN_PASS_STATS):
            for batch, mask in ingest.batches(source):
                c, x_dev, m_dev = ingest.put(batch, mask, dtype)
                with ingest.accumulate(
                        accumulate_path(stats[c].gram, x_dev, m_dev)):
                    stats[c] = update_stats_auto(stats[c], x_dev, m_dev,
                                                 precision=precision)
    finally:
        ingest.release()
    ingest.set_data(n)
    if len(devices) > 1:
        return collective_stats(ingest, stats)
    return stats[0]


def stream_covariance(
    source,
    mean_centering: bool = True,
    dtype=jnp.float32,
    device=None,
    precision: Optional[str] = None,
    ingest: Optional[IngestTrace] = None,
):
    """Stream a ``data.batches.BatchSource`` into (covariance, mean, count).

    Two-pass (center → Gram) when the source is re-iterable and centering is
    requested; one-pass sufficient statistics otherwise. The two-pass fit
    does not wait for the mean to start on the Gram: pass 1 runs, behind each
    batch's mean step, the centred-Gram step pass 2 would run, about the mean
    of the chip's first batch, and ``recentre_gram`` moves the sum to the
    mean of all rows after the last batch — one rank-one correction, exact in
    the algebra, so the Gram steps run while the rows still cross. The same
    program reads from the rows how much of the shifted sum the correction
    took away (ρ; ≈ 1/batchRows for rows in any order that is not sorted by
    value), and the host's one blocking read (``stream:sync/count``) fetches
    it with the count: at ρ ≤ ``SHIFT_RATIO_MAX`` the fit is done, in one
    walk of the rows (``passes`` 1, ``gram_shift`` {accepted, ratio} in the
    counters). Otherwise — a frame sorted by a feature, a drifting stream —
    the shifted Gram is dropped and pass 2 runs as it always has, about the
    mean of all rows: pass 1 keeps its device batches while
    ``keep_budget_bytes`` has room and pass 2 runs on those, so only the rows
    past the budget are walked and put a second time (all of them where the
    backend reports no memory, as on the CPU), and a factory that hands back
    a stale iterator there still raises. Returns device arrays; covariance is
    normalized by n−1 as everywhere in this package.
    A chip has at most two puts in flight (``PUTS_IN_FLIGHT``; the third
    waits inside ``IngestTrace.put`` for the first to land): the v5e traces
    show a landed batch's step starting only after every transfer queued
    behind it, so a loop that issues all its puts at once runs every step
    after the last landing, and one that issues two runs step i under
    crossing i+2. What crosses, and what each step sums, is a row slice of
    a host batch (``IngestTrace.batches``: equal slices of at most
    ``PUT_BYTES_MAX``), so that the steps left behind the last landing and
    the first put's re-tiling are those of a slice.
    A batch the source assembles by a host copy is written into a staging
    buffer lent by the ``IngestTrace`` out of the process's ``STAGING`` pool
    and taken back when the puts of all its slices have landed
    (``StagingPool``): the process keeps up to (``PUTS_IN_FLIGHT`` + 1) ×
    chips such buffers between fits.
    ``device`` is one chip or a sequence of them. Over several, the slices
    are dealt to the chips in turn; each chip sums its own
    with the programs the one-chip fit runs (about its own first batch's
    mean), keeps its own batches under its own budget, and the chips meet in
    two all-reduces: after pass 1 the column sums and counts (every chip gets
    the mean of all rows and re-centres its own Gram about it), then the
    Grams (one covariance, on the first chip, where the results are
    returned; ρ is the chips' largest, and a refused shift sums pass 2's
    Grams in a third). A one-pass fit has the second only. One chip runs no
    mesh program at all.
    ``ingest`` (an ``IngestTrace`` on the fit's ``PhaseTimer``; it then names
    the chips) records the stages; without one they are traced and counted
    all the same. Each Gram step asks ``accumulate_path`` for its span's
    name and then calls ``update_*_auto``, which asks again: the step stays
    the one function other callers and the benchmark's fault tests reach.
    """
    if ingest is None:
        ingest = IngestTrace(device=device)
    devices = ingest.devices
    several = len(devices) > 1
    n = source.n_features
    if mean_centering and source.reiterable:
        mstats = [MeanStats(jnp.zeros((n,), dtype=dtype, device=d),
                            jnp.zeros((), dtype=jnp.int32, device=d))
                  for d in devices]
        itemsize = jnp.dtype(dtype).itemsize
        ingest.allow_keep(gram_reserve_bytes(source.batch_rows * n * itemsize,
                                             n * n * itemsize))

        def new_grams():
            return [jnp.zeros((n, n), dtype=dtype, device=d) for d in devices]

        def meet(grams):
            """The chips' Grams as one, on the first chip."""
            if several:
                return collective_sum(ingest, [(g,) for g in grams])[0]
            return grams[0]

        grams = new_grams()
        # a chip's c: the mean of its first batch with a row (0 before it:
        # a slice of a padded tail may hold padding only)
        shifts = [jnp.zeros((n,), dtype=dtype, device=d) for d in devices]
        shifted = [False] * len(devices)
        try:
            with ingest.walk(SPAN_PASS_MEAN):
                for batch, mask in ingest.batches(source):
                    c, x_dev, m_dev = ingest.put(batch, mask, dtype)
                    with ingest.accumulate("mean"):
                        mstats[c] = update_mean_stats(mstats[c], x_dev, m_dev)
                    if not shifted[c] and ingest.put_rows:
                        shifts[c] = mstats[c].col_sum / mstats[c].count
                        shifted[c] = True
                    with ingest.accumulate(
                            accumulate_path(grams[c], x_dev, m_dev)):
                        grams[c] = update_centered_gram_auto(
                            grams[c], x_dev, shifts[c], m_dev,
                            precision=precision)
                    ingest.keep(c, x_dev, m_dev)  # for the fallback
            if several:
                means, count = collective_mean(ingest, mstats)
            else:
                count = mstats[0].count
                means = [mstats[0].col_sum / count]
            ratios = []
            for c, stats in enumerate(mstats):
                # (a chip no row came to: G = 0 about c = 0)
                grams[c], ratio = recentre_gram(
                    grams[c], stats.col_sum, stats.count, shifts[c], means[c])
                ratios.append(ratio)
            gram_acc = meet(grams)
            with ingest.sync(SPAN_SYNC_COUNT):
                pass1_rows, *ratios = (
                    v.item() for v in jax.device_get([count, *ratios]))
            if not ingest.shift_verdict(ratios):
                # the rows say the shift was poor: pass 2 over the kept
                # batches (and the rest of the source, put again), as if
                # pass 1 had summed nothing but the mean
                del gram_acc
                grams = new_grams()
                with ingest.walk(SPAN_PASS_GRAM):
                    for c, x_dev, m_dev in ingest.replay(source, dtype):
                        with ingest.accumulate(
                                accumulate_path(grams[c], x_dev, m_dev)):
                            grams[c] = update_centered_gram_auto(
                                grams[c], x_dev, means[c], m_dev,
                                precision=precision)
                gram_acc = meet(grams)
                if ingest.pass_rows != pass1_rows:
                    # A "re-iterable" factory that hands back a
                    # partially-consumed iterator would silently zero the
                    # Gram; fail instead.
                    raise RuntimeError(
                        f"two-pass streaming saw {pass1_rows} rows on pass "
                        f"1 but {ingest.pass_rows} on pass 2; the source "
                        f"factory must return a FRESH iterator on every call"
                    )
        finally:
            ingest.release()  # no device batch outlives the walks over it
        ingest.set_data(n)
        denom = jnp.maximum(count - 1, 1)
        return gram_acc / denom, means[0], count

    total = stream_gram_stats(source, dtype=dtype, precision=precision,
                              ingest=ingest)
    cov = covariance_from_stats(
        total.gram, total.col_sum, total.count, mean_centering=mean_centering
    )
    if mean_centering:
        mean = total.col_sum / total.count
    else:
        mean = jnp.zeros_like(total.col_sum)
    return cov, mean, total.count
