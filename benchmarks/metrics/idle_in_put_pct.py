"""Share of the chip's idle seconds in the traced window during which the
main thread was inside ``stream:put`` (innermost program span over the
gap's middle), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.idle_share_pct(ctx, ("stream:put",))
