"""Share of the traced window in which no op ran on the chip, in percent:
1 - union of the device's op intervals over the window."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["window_s"] or trace["busy_s"] is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
