"""Share of the window's fit wall that the driver spent merging the
partitions' statistics (``fit_timings_["stage/merge"]``: the span
``stage:merge`` around reading the collected rows, their float64 sum and
the centring), in percent. None where the program reports no such key."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    stage = ctx["load_module"]("work/stage.py")
    return spans.phase_share_pct(ctx["fits"], stage.PHASES["merge"])
