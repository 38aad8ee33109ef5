"""Share of the chip's idle seconds in the traced window during which the
main thread was handing a task's statistics back or merging them on the
driver (innermost span over the gap's middle ``stage:handback`` or
``stage:merge``, with the stage's spans kept beside the listed program
spans: ``work/stage.py``), in percent."""


def read(ctx):
    stage = ctx["load_module"]("work/stage.py")
    return stage.idle_share_pct(ctx, ("handback", "merge"))
