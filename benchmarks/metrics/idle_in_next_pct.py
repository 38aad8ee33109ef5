"""Share of the chip's idle seconds in the traced window during which the
main thread was inside ``stream:next`` (innermost listed program span over
the gap's middle; the read and copy spans inside it are not listed, so
their idle seconds count here), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    reblock = ctx["load_module"]("work/reblock.py")
    return spans.idle_share_pct(ctx, (reblock.NEXT_SPAN,))
