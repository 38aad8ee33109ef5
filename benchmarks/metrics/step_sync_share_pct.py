"""Share of the window's fit wall that the main thread spent in the Lloyd
steps' host reads of the centres' shift (``extra["lloyd"]
["sync_seconds"]``, the span ``lloyd:sync``: one a step, blocked until the
step's programs ran), in percent. With the rows kept on the chip nothing
else waits for the device inside a step, so this is the steps' device
time as the host sees it. None where the program reports no such
counter."""


def read(ctx):
    lloyd = ctx["load_module"]("work/lloyd.py").window_lloyd(ctx)
    wall = sum(f["wall"] for f in ctx["fits"])
    if lloyd is None or not wall:
        return None
    return 100.0 * sum(fit["sync_seconds"] for fit in lloyd) / wall
