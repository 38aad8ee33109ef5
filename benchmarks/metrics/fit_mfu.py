"""The whole fit's share of the chips' peak FLOP/s, in percent: the
algorithm's operations (the configuration's work module, ``ctx["work"]``)
of every fit completed in the window, over the window, over the bf16 peak
of every chip the cell holds. It bounds every kernel's roofline from the
end-to-end side: host time, transfers and the solve all count against it."""


def read(ctx):
    if not ctx["peak"] or not ctx["window_s"]:
        return None
    flops = ctx["work"].flops(ctx["rows_per_fit"] * len(ctx["fits"]),
                              ctx["n_features"])
    peak = ctx["chips"] * ctx["peak"]["flops_per_s_bf16"]
    return 100.0 * flops / ctx["window_s"] / peak
