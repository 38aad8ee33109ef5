"""Work of a Lloyd fit (k-means) as a configuration runs it, from shapes
and the configuration's ``k`` and ``maxIter``; the names under which the
fit shows in a trace and in its report.

One Lloyd step over ``rows`` rows of ``n`` features is two products with
the (k, n) centres — the distances' cross term and the per-cluster sums —
``4 * rows * k * n`` operations, reading every row once (``rows * n * 4``
bytes in float32). A fit runs ``maxIter`` steps (a configuration whose
``tol`` no float32 centre meets runs every one of them) and a cost pass
under the final centres, a walk of the same program: ``maxIter + 1`` walks,
all counted. Drawing the initial centres (k rows gathered) and the step's
end (``next_centers``, k × n) are the implementation's, not counted.

``bind(config)`` takes ``k`` and ``maxIter`` from the configuration's
Params (the harness calls it). ``PROGRAMS`` names the step's program
(``ops/kmeans_kernel.update_cluster_stats``, traced as
``jit_update_cluster_stats``), which ``accumulate_roofline`` reads.
``SPANS`` are the host spans of the fit's Lloyd loop
(``models/kmeans.py`` ``LLOYD_SPANS``; a test of the program holds the two
equal); they are not in ``work/spans.py``'s ``PROGRAM_SPANS``, so the
accepted idle readers count their idle seconds as unattributed.
``window_lloyd`` reads each fit's ``extra["lloyd"]`` (``steps``,
``batches_from_kept``, ``batches_put_again``, ``sync_seconds``) through the
program's ring of reports (``work/crossing.py``).
"""

from __future__ import annotations

from types import SimpleNamespace

PROGRAMS = ("update_cluster_stats",)
SPANS = ("lloyd:init", "lloyd:step", "lloyd:dispatch", "lloyd:sync",
         "lloyd:cost")
COUNTERS = "lloyd"


def bind(config: dict) -> SimpleNamespace:
    k = int(config["params"]["k"])
    walks = int(config["params"]["maxIter"]) + 1

    def flops(rows: int, n: int) -> int:
        return walks * 4 * rows * k * n

    def bytes_read(rows: int, n: int, itemsize: int = 4) -> int:
        return walks * rows * n * itemsize

    def least_seconds(rows: int, n: int, peak: dict) -> tuple:
        t_flops = flops(rows, n) / peak["flops_per_s_bf16"]
        t_bytes = bytes_read(rows, n) / peak["hbm_bytes_per_s"]
        return ((t_flops, "compute") if t_flops >= t_bytes
                else (t_bytes, "memory"))

    return SimpleNamespace(k=k, walks=walks, flops=flops,
                           bytes_read=bytes_read,
                           least_seconds=least_seconds, PROGRAMS=PROGRAMS)


def window_lloyd(ctx: dict):
    """``extra["lloyd"]`` of each of the window's fits; None where the
    program has no door for its reports or a fit has no such counters (a
    KMeans that is not on the streamed loop)."""
    reports = ctx["load_module"]("work/crossing.py").window_reports(ctx)
    if reports is None:
        return None
    lloyd = [r.get("extra", {}).get(COUNTERS) for r in reports]
    return None if any(c is None for c in lloyd) else lloyd
