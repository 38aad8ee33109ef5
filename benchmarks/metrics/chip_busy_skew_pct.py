"""How unevenly the fit's chips were busy in the traced window, in
percent: (the busiest chip's busy seconds - the least busy chip's) over
the busiest's, from ``xplane.busy``'s per-chip intervals. Dealing the
batches in turn should keep it near 0; the first chip also runs the
solve."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] is None:
        return None
    xplane = ctx["load_module"]("xplane.py")
    state = xplane.busy(trace["planes"], trace["lo"], trace["hi"])
    seconds = [sum(b - a for a, b in chip) / 1e9
               for chip in state["intervals"]]
    if len(seconds) < 2 or not max(seconds):
        return None
    return 100.0 * (max(seconds) - min(seconds)) / max(seconds)
