"""Bytes handed to ``device_put`` over the seconds of the estimator's
``covariance`` phase (``fit_timings_["covariance"]``), in GB/s. Transfer
and accumulate kernels both sit inside that phase and cannot be told
apart from outside the program, so this is the phase's rate, not the
link's. The bytes are the program's own count (``extra["ingest"]
["bytes_put"]`` through ``work/crossing.py`` ``window_ingest``: a kept
batch once); the traffic's static ``crossings`` only where the program
has no reports to read."""


def read(ctx):
    seconds = [f["timings"].get("covariance") for f in ctx["fits"]]
    if not seconds or any(s is None for s in seconds) or not sum(seconds):
        return None
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    total = None if ingest is None else crossing.counter_sum(ingest,
                                                             "bytes_put")
    if total is None:
        total = ctx["bytes_put_per_fit"] * len(seconds)
    return total / sum(seconds) / 1e9
