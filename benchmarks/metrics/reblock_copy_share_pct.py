"""Share of the window's fit wall spent in the host copies of re-blocking
(``fit_timings_["covariance/next/copy"]``: the spans ``stream:next/copy``
around each ``np.concatenate`` of chunks into a device batch and each padded
tail), in percent; 0 where every batch is a slice of one chunk. None where
the program reports no such key (a parent without the span)."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    reblock = ctx["load_module"]("work/reblock.py")
    return spans.phase_share_pct(ctx["fits"], reblock.PHASES["copy"])
