"""Work of a Lloyd fit (k-means), from shapes and the configuration's
``k`` (a test fixture's).

One Lloyd step over ``rows`` rows of ``n`` features is two products with
the (k, n) centres: the distances' cross term and the per-cluster sums,
``4 * rows * k * n`` operations, reading every row once. A fit makes as
many steps as it needs to stand still, and a seeding and a cost pass
besides; this counts ONE step a fit, the least any Lloyd fit does, so
``fit_mfu`` reads a floor of the fit's share, never more than it has.

``bind(config)`` takes ``k`` from the configuration's Params (the harness
calls it); ``PROGRAMS`` names the accumulate program of the streamed fit
(``ops/kmeans_kernel.update_cluster_stats``).
"""

from __future__ import annotations

from types import SimpleNamespace

PROGRAMS = ("update_cluster_stats",)


def bind(config: dict) -> SimpleNamespace:
    k = int(config["params"]["k"])

    def flops(rows: int, n: int) -> int:
        return 4 * rows * k * n

    def least_seconds(rows: int, n: int, peak: dict) -> tuple:
        t_flops = flops(rows, n) / peak["flops_per_s_bf16"]
        t_bytes = rows * n * 4 / peak["hbm_bytes_per_s"]
        return ((t_flops, "compute") if t_flops >= t_bytes
                else (t_bytes, "memory"))

    return SimpleNamespace(k=k, flops=flops, least_seconds=least_seconds,
                           PROGRAMS=PROGRAMS)
