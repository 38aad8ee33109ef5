"""Share of the window's fit wall after the solve
(``fit_timings_["fetch"]``: pc, explained variance and mean to the host,
float64 copies, the ``PCAModel``), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    return spans.phase_share_pct(ctx["fits"], "fetch")
