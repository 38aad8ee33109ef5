"""Work of the covariance-accumulate kernel family, from shapes alone.

The algorithm's work, whatever kernel runs it: a symmetric Gram over
``rows`` rows of ``n`` features needs ``rows * n * (n + 1)`` floating-point
operations (the multiply-adds of the upper triangle with its diagonal,
two operations each) and has to read every row once, ``rows * n * 4``
bytes in float32. A mean pass, a second read of the rows, a centred copy
or extra MXU passes for precision are the implementation's cost, not the
algorithm's, and so lower the roofline share instead of raising the work.

``PROGRAMS`` are the names under which the family's programs appear in a
device trace (substring match on the traced module or op name).
"""

from __future__ import annotations

PROGRAMS = (
    "update_centered_gram",          # XLA dot_general and the Pallas
    "update_stats",                  # ..._fused_blocked variants match too
    "update_mean_stats",
)


def flops(rows: int, n: int) -> int:
    return rows * n * (n + 1)


def bytes_read(rows: int, n: int, itemsize: int = 4) -> int:
    return rows * n * itemsize


def least_seconds(rows: int, n: int, peak: dict) -> tuple:
    """(seconds, which) — the least time one chip could take, and whether
    ``compute`` or ``memory`` bounds it."""
    t_flops = flops(rows, n) / peak["flops_per_s_bf16"]
    t_bytes = bytes_read(rows, n) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
