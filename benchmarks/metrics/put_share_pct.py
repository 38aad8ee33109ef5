"""Share of the window's fit wall that the main thread spent in
``stream:put`` (``fit_timings_["covariance/put"]``: ``np.asarray`` and the
``device_put`` calls of every batch), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.phase_share_pct(ctx["fits"], "covariance/put")
