"""Share of the window's copied batches that were written into a staging
buffer the pool already had (``staging_reused`` over ``staging_reused`` +
``staging_fresh`` of ``extra["ingest"]``), in percent: 100 from a process's
second fit on. None where the program has no door for its reports, or where
no batch was copied (a cell fed whole chunks)."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    reused = crossing.counter_sum(ingest, "staging_reused")
    fresh = crossing.counter_sum(ingest, "staging_fresh")
    if reused is None or fresh is None or not reused + fresh:
        return None
    return 100.0 * reused / (reused + fresh)
