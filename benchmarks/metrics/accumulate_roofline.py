"""Roofline share of the covariance-accumulate programs: the least time
the chip could take for the algorithm's work (``work/gram.py``) over the
device time those programs took in the traced window, in percent."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not ctx["peak"]:
        return None
    gram = ctx["load_module"]("work/gram.py")
    xplane = ctx["load_module"]("xplane.py")
    seconds = sum(xplane.program_seconds(
        trace["planes"], gram.PROGRAMS, trace["lo"], trace["hi"]).values())
    if not seconds:
        return None
    least, _ = gram.least_seconds(
        ctx["rows_per_fit"] * len(ctx["fits"]), ctx["n_features"], ctx["peak"])
    return 100.0 * least / seconds
