"""The longest single fit of the window: a stall the rate averages away."""


def read(ctx):
    return max(f["wall"] for f in ctx["fits"]) if ctx["fits"] else None
