"""Share of the window's fit wall that the main thread spent dispatching
the fit's all-reduces (``fit_timings_["covariance/collective"]``: the spans
``stream:collective/mean`` and ``/gram``), in percent. The dispatch only:
the device's own time is ``collective_device_ms_per_fit``."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    collective = ctx["load_module"]("work/collective.py")
    return spans.phase_share_pct(ctx["fits"], collective.PHASE)
