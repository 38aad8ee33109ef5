"""Bytes a fit handed to ``device_put`` (``extra["ingest"]["bytes_put"]``:
the program's own count, padding included, a kept batch once), mean of the
window's fits, in GB. With the keep engaged a two-pass fit reads what a
one-pass fit reads. None where the program has no door for its reports."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    total = None if ingest is None else crossing.counter_sum(ingest,
                                                             "bytes_put")
    return None if total is None else total / len(ingest) / 1e9
