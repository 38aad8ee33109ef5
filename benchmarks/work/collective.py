"""The two all-reduces of a fit over several chips, by name, and the bytes
each moves, from shapes alone.

``PROGRAMS`` are the names under which the mesh programs appear in a device
trace (substring match on the traced module name ``jit_<function>``, as
``work/gram.py`` does for the accumulate family):
``spark_rapids_ml_tpu/parallel/mesh.py`` ``all_reduce_mean`` (after pass 1:
every chip's column sums and count in, the mean of all rows out on every
chip) and ``all_reduce_sum`` (after pass 2: the chips' n x n Grams in, their
sum out; in a one-pass fit the column sums and counts ride along).
``SPANS`` are the host spans that wrap exactly the dispatch of each
(``ops/streaming.py``: ``SPAN_COLLECTIVE``), ``PHASE`` the ``fit_timings_``
key their seconds are summed under. A test of the program holds all of
them against what it emits.

The bytes are one chip's operand, what ``parallel.mesh.collective_nbytes``
reckons and the fit counts in ``fit_report_.extra["ingest"]
["collective_bytes"]``. There is no roofline here: ``peaks.json`` holds no
sourced chip-to-chip figure.
"""

from __future__ import annotations

PROGRAMS = ("all_reduce_mean", "all_reduce_sum")
SPANS = {"mean": "stream:collective/mean", "gram": "stream:collective/gram"}
PHASE = "covariance/collective"


def mean_bytes(n: int, itemsize: int = 4) -> int:
    """Collective (a): n column sums and the row count (int32)."""
    return n * itemsize + 4


def gram_bytes(n: int, itemsize: int = 4) -> int:
    """Collective (b) of a two-pass fit: one n x n Gram."""
    return n * n * itemsize


def device_seconds(ctx) -> dict:
    """{traced program: device seconds in the traced window, summed over
    the chips} of the collectives; empty without a device trace or where
    the program runs none (one chip, or a parent without them)."""
    trace = ctx["trace"]
    if not trace or trace["busy_s"] is None:
        return {}
    xplane = ctx["load_module"]("xplane.py")
    return xplane.program_seconds(trace["planes"], PROGRAMS, trace["lo"],
                                  trace["hi"])
