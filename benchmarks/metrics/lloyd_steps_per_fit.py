"""Lloyd steps a fit of the window ran (``extra["lloyd"]["steps"]``: the
program's own count), mean of the window's fits: ``maxIter`` in a cell
whose ``tol`` no float32 centre meets, fewer where a fit reached a fixed
point. None where the program reports no such counter."""


def read(ctx):
    lloyd = ctx["load_module"]("work/lloyd.py").window_lloyd(ctx)
    if lloyd is None:
        return None
    return sum(fit["steps"] for fit in lloyd) / len(lloyd)
