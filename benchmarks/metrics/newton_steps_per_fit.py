"""Newton steps a fit of the window ran (``extra["newton"]["steps"]``: the
program's own count), mean of the window's fits: ``maxIter`` in a cell
whose ``tol`` no float32 step meets, fewer where a fit met it. None where
the program reports no such counter."""


def read(ctx):
    newton = ctx["load_module"]("work/newton.py").window_newton(ctx)
    if newton is None:
        return None
    return sum(fit["steps"] for fit in newton) / len(newton)
