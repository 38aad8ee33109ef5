"""The whole fit's share of the chip's peak FLOP/s, in percent: the
algorithm's Gram operations (``work/gram.py``) of every fit completed in
the window, over the window, over the bf16 peak. It bounds every kernel's
roofline from the end-to-end side: host time, transfers and the solve all
count against it."""


def read(ctx):
    if not ctx["peak"] or not ctx["window_s"]:
        return None
    gram = ctx["load_module"]("work/gram.py")
    flops = gram.flops(ctx["rows_per_fit"] * len(ctx["fits"]),
                       ctx["n_features"])
    return 100.0 * flops / ctx["window_s"] / ctx["peak"]["flops_per_s_bf16"]
