"""Share of the window's fit wall that the main thread spent in the Newton
steps' host reads (``extra["newton"]["sync_seconds"]``, the span
``newton:sync``: one a step, blocked until the step's weighted Gram and
its finishing program ran), in percent. With the rows kept on the chip
nothing else waits for the device inside a step, so this is the steps'
device time as the host sees it. None where the program reports no such
counter."""


def read(ctx):
    newton = ctx["load_module"]("work/newton.py").window_newton(ctx)
    wall = sum(f["wall"] for f in ctx["fits"])
    if newton is None or not wall:
        return None
    return 100.0 * sum(fit["sync_seconds"] for fit in newton) / wall
