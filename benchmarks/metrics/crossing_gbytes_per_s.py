"""The link's rate as the program counts it: per chip, the bytes the
window's fits put there (``per_chip[].bytes_put``) over the seconds a put of
that chip was outstanding (``per_chip[].crossing_seconds``: re-tiling, queue
and crossing), mean of the chips, in GB/s. Beside ``covariance_gbytes_per_s``,
which times the whole phase from outside and reckons the bytes from the
traffic file. None where the program has no door for its reports or no
landing counters."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    chips: dict = {}
    for fit in ingest:
        for chip in fit.get("per_chip", ()):
            if "crossing_seconds" not in chip:
                return None
            total = chips.setdefault(chip["device"], [0, 0.0])
            total[0] += chip["bytes_put"]
            total[1] += chip["crossing_seconds"]
    rates = [b / s / 1e9 for b, s in chips.values() if s]
    return sum(rates) / len(rates) if rates else None
