"""Share of the window's fit wall during which a put of the fit's fullest
chip was outstanding (``fit_timings_["covariance/crossing"]``: the summed
landing spans ``stream:landing/<id>`` of that chip, each from the put's
``device_put`` returning — or the chip's previous landing — to its own), in
percent. It overlaps the main thread's phases: what it leaves of the wall is
the time the link had nothing to carry. None where the program reports no
such key."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    crossing = ctx["load_module"]("work/crossing.py")
    return spans.phase_share_pct(ctx["fits"], crossing.CROSSING_PHASE)
