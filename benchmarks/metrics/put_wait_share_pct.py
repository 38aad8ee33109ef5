"""Share of the window's fit wall that the main thread spent waiting for a
chip's put window (``extra["ingest"]["put_wait_seconds"]``: the part of
``covariance/put`` that is the wait for the chip's oldest put to land, not
``np.asarray`` and ``device_put``), in percent. None where the program has
no door for its reports."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    wall = sum(f["wall"] for f in ctx["fits"])
    waited = None if ingest is None else crossing.counter_sum(
        ingest, "put_wait_seconds")
    return None if waited is None or not wall else 100.0 * waited / wall
