"""Rows for both sides of the two-pass fit's verdict (not a test file).

A two-pass streamed fit sums its Gram about the mean of each chip's first
batch and keeps that sum only where the rows say the shift was close to
the mean of all rows (``ops/streaming.SHIFT_RATIO_MAX``). The small i.i.d.
batches of these suites (32 or 64 rows) refuse it by their nature — a
batch's mean is off by σ/√rows, ρ ≈ χ²₁/rows a column — so every case
written before the shift stays on the fallback, pass 2, as it is.
``mirrored_pairs`` makes the same shapes take the other side: rows 2i and
2i+1 of every chunk mirror each other about ``centre``, so any batch that
starts at an even row and holds an even number of valid rows has the mean
``centre`` to rounding, whatever its size.
"""

from __future__ import annotations

import numpy as np


def mirrored_pairs(chunks: list, centre) -> list:
    """``chunks`` (each an even number of rows) with every odd row the
    mirror image of the row before it about ``centre``."""
    out = []
    for x in chunks:
        assert x.shape[0] % 2 == 0, x.shape
        y = np.array(x)
        spread = x[0::2] - centre
        y[0::2] = centre + spread
        y[1::2] = centre - spread
        out.append(y)
    return out


def verdict(ingest) -> tuple:
    """(accepted, passes) of a two-pass fit's counters (an ``IngestTrace``'s
    or a report's ``extra["ingest"]``)."""
    counters = getattr(ingest, "counters", ingest)
    return counters["gram_shift"]["accepted"], counters["passes"]
