"""Device milliseconds a fit spends in its all-reduce programs
(``work/collective.py``), summed over the chips: the traced window's
seconds of those programs over the fits completed in it. A chip that
reaches the all-reduce before the others waits inside it, so this holds
the chips' skew at the meeting points as well as the bytes' crossing."""


def read(ctx):
    seconds = ctx["load_module"]("work/collective.py").device_seconds(ctx)
    if not seconds or not ctx["fits"]:
        return None
    return 1e3 * sum(seconds.values()) / len(ctx["fits"])
