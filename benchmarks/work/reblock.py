"""The host's side of ``stream:next``, by name: reading a chunk and
re-blocking chunks into device batches.

``stream:next`` (in ``work/spans.py``'s ``PROGRAM_SPANS``) wraps one
``next()`` on the source's batches; its seconds are
``fit_timings_["covariance/next"]``. Inside it the source itself
(``spark_rapids_ml_tpu/data/batches.py`` ``BatchSource.batches``, named by
``ops/streaming.py`` ``SPAN_NEXT_PART`` / ``PHASE_NEXT_PART``) emits
``SPANS["read"]`` around the pull of the next chunk from the dataset and its
reading into a 2-D array (``data/arrow.py`` for an Arrow record batch: a
view), and ``SPANS["copy"]`` around each host copy of re-blocking (a device
batch assembled by ``np.concatenate`` from chunks that do not align with
``batchRows``, or a padded tail); ``PHASES`` are the ``fit_timings_`` keys
their seconds are summed under, 0.0 in a fit that read or copied nothing.
A test of the program holds all of them against what it emits.

The two names are deliberately NOT in ``PROGRAM_SPANS``: the idle readers
keep only the listed spans on the host planes, so a chip-idle second under
``stream:next/copy`` goes to the enclosing ``stream:next``, which is what
``idle_in_next_pct`` asks for.

No roofline: the re-blocking is a host memory copy and ``peaks.json`` holds
no sourced host-memory figure. Its rate (bytes re-blocked over
``PHASES["copy"]`` seconds) is in ``PERF.md``.
"""

from __future__ import annotations

NEXT_SPAN = "stream:next"
NEXT_PHASE = "covariance/next"
SPANS = {"read": "stream:next/read", "copy": "stream:next/copy"}
PHASES = {"read": "covariance/next/read", "copy": "covariance/next/copy"}
