"""Share of the window's fit wall that the main thread spent blocked on a
device value inside the ``covariance`` phase
(``fit_timings_["covariance/sync"]``: ``stream:sync/count`` and
``stream:sync/cov``), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.phase_share_pct(ctx["fits"], "covariance/sync")
