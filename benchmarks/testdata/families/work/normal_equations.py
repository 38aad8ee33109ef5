"""Work of a least-squares fit by the normal equations, from shapes alone
(a test fixture's).

The algorithm's work is the symmetric Gram of Z = [X | y] over ``rows``
rows of ``n`` features and one label: ``rows * (n + 1) * (n + 2)``
operations (the upper triangle with its diagonal, two a multiply-add),
reading every row once, ``rows * (n + 1) * 4`` bytes in float32. The
(n + 1)-wide solve is the host's and negligible beside it.

``PROGRAMS``: the accumulate program the streamed fit runs
(``ops/streaming.update_stats``), as a substring of the traced name.
"""

from __future__ import annotations

PROGRAMS = ("update_stats",)


def flops(rows: int, n: int) -> int:
    return rows * (n + 1) * (n + 2)


def bytes_read(rows: int, n: int, itemsize: int = 4) -> int:
    return rows * (n + 1) * itemsize


def least_seconds(rows: int, n: int, peak: dict) -> tuple:
    """(seconds, which) — the least time one chip could take, and whether
    ``compute`` or ``memory`` bounds it."""
    t_flops = flops(rows, n) / peak["flops_per_s_bf16"]
    t_bytes = bytes_read(rows, n) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
