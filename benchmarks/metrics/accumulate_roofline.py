"""Roofline share of the accumulate programs: the least time one chip
could take for the algorithm's work (the configuration's work module,
``ctx["work"]``) over the device time its ``PROGRAMS`` took in the traced
window, summed over the chips, in percent. Nothing where the work module
names no programs."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not ctx["peak"]:
        return None
    work = ctx["work"]
    programs = getattr(work, "PROGRAMS", None)
    if not programs:
        return None
    xplane = ctx["load_module"]("xplane.py")
    seconds = sum(xplane.program_seconds(
        trace["planes"], programs, trace["lo"], trace["hi"]).values())
    if not seconds:
        return None
    least, _ = work.least_seconds(
        ctx["rows_per_fit"] * len(ctx["fits"]), ctx["n_features"], ctx["peak"])
    return 100.0 * least / seconds
