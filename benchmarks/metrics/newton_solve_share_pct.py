"""Share of the window's fit wall that the steps' finishing program took as
the host sees it (``extra["newton"]["solve_seconds"]``: inside
``newton:sync``, from the step's summed partials being ready to max|Δ|
read — the system's assembly and its Cholesky solve on the chip), in
percent. Part of ``newton_sync_share_pct``; the rest of that is the
weighted Gram the host waited for. None where the program reports no such
counter."""


def read(ctx):
    newton = ctx["load_module"]("work/newton.py").window_newton(ctx)
    wall = sum(f["wall"] for f in ctx["fits"])
    if newton is None or not wall:
        return None
    return 100.0 * sum(fit["solve_seconds"] for fit in newton) / wall
