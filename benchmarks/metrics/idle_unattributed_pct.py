"""Share of the chip's idle seconds in the traced window under no program
span finer than ``bench_fit`` / ``fit:pca`` / ``streamed cov``, in
percent: how much of the idle time the instrument cannot name."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.idle_share_pct(ctx, spans.COARSE + (spans.NO_SPAN,),
                                require_span=False)
