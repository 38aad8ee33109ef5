"""Share of the window's fit wall spent pulling chunks from the dataset and
reading each into a 2-D array (``fit_timings_["covariance/next/read"]``:
the spans ``stream:next/read``; for an Arrow record batch the reader of
``data/arrow.py``), in percent. None where the program reports no such key
(a parent without the span)."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    reblock = ctx["load_module"]("work/reblock.py")
    return spans.phase_share_pct(ctx["fits"], reblock.PHASES["read"])
