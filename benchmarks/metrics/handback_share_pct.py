"""Share of the window's fit wall that the executor tasks spent handing
their statistics back (``fit_timings_["stage/handback"]``: the spans
``stage:handback`` around the Gram's device-to-host fetch, its float64 form
and the stats row's Arrow batch), in percent. None where the program
reports no such key (a parent without the span, or tasks in other
processes)."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    stage = ctx["load_module"]("work/stage.py")
    return spans.phase_share_pct(ctx["fits"], stage.PHASES["handback"])
