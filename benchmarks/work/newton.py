"""Work of a binary logistic regression fitted by Newton's method as a
configuration runs it, from shapes and the configuration's ``maxIter``;
the names under which the fit shows in a trace and in its report.

One Newton step over ``rows`` rows of ``n`` features: the weighted Gram
XᵀWX, ``2 * rows * n * n`` operations, and the rows' three passes of
matrix-vector work — the logits Xw, the gradient Xᵀr and the Hessian's
intercept column Xᵀs — ``6 * rows * n``, reading every ``[X | y]`` row once
(``rows * (n + 1) * 4`` bytes in float32). A fit runs ``maxIter`` steps (a
configuration whose ``tol`` no float32 step meets runs every one of
them). The (n + 1)² system's assembly and its Cholesky solve, once a step,
are the implementation's share of the step, not counted.

``bind(config)`` takes ``maxIter`` from the configuration's Params (the
harness calls it). ``PROGRAMS`` names the step's program
(``ops/logreg_kernel.update_logreg_stats``, traced as
``jit_update_logreg_stats``), which ``accumulate_roofline`` reads.
``SPANS`` are the host spans of the fit's Newton loop
(``models/logistic_regression.py`` ``NEWTON_SPANS``; a test of the program
holds the two equal); they are not in ``work/spans.py``'s
``PROGRAM_SPANS``, so the accepted idle readers count their idle seconds
as unattributed. ``window_newton`` reads each fit's ``extra["newton"]``
(``steps``, ``batches_from_kept``, ``batches_put_again``,
``sync_seconds``, ``solve_seconds``, ``final_step_max``) through the
program's ring of reports (``work/crossing.py``).
"""

from __future__ import annotations

from types import SimpleNamespace

PROGRAMS = ("update_logreg_stats",)
SPANS = ("newton:init", "newton:step", "newton:dispatch", "newton:sync")
COUNTERS = "newton"


def bind(config: dict) -> SimpleNamespace:
    steps = int(config["params"]["maxIter"])

    def flops(rows: int, n: int) -> int:
        return steps * (2 * rows * n * n + 6 * rows * n)

    def bytes_read(rows: int, n: int, itemsize: int = 4) -> int:
        return steps * rows * (n + 1) * itemsize

    def least_seconds(rows: int, n: int, peak: dict) -> tuple:
        t_flops = flops(rows, n) / peak["flops_per_s_bf16"]
        t_bytes = bytes_read(rows, n) / peak["hbm_bytes_per_s"]
        return ((t_flops, "compute") if t_flops >= t_bytes
                else (t_bytes, "memory"))

    return SimpleNamespace(steps=steps, flops=flops, bytes_read=bytes_read,
                           least_seconds=least_seconds, PROGRAMS=PROGRAMS)


def window_newton(ctx: dict):
    """``extra["newton"]`` of each of the window's fits; None where the
    program has no door for its reports or a fit has no such counters (a
    LogisticRegression that is not on the streamed loop)."""
    reports = ctx["load_module"]("work/crossing.py").window_reports(ctx)
    if reports is None:
        return None
    newton = [r.get("extra", {}).get(COUNTERS) for r in reports]
    return None if any(c is None for c in newton) else newton
