"""What a fit leaves behind the link: the median over the window's fits of
``extra["ingest"]["landing_tail_seconds"]`` — from the fit's last landing
(the landing watcher's clock, the latest chip's) to ``all_landed``, which a
PCA fit calls right after its covariance read, so the device work and reads
no crossing hides — in milliseconds. Summed over the tasks of a front fit,
as its ``landings`` are. None where the program has no door for its
reports or a fit has no such counter (a program without it)."""

import statistics


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    tails = [fit.get("landing_tail_seconds") for fit in ingest]
    if any(t is None for t in tails):
        return None
    return 1000.0 * statistics.median(tails)
