"""Share of the chip's idle seconds in the traced window during which the
main thread was blocked in ``stream:sync/count`` or ``stream:sync/cov``,
together, in percent (each is logged to stderr)."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.idle_share_pct(
        ctx, ("stream:sync/count", "stream:sync/cov"))
