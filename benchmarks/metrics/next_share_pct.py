"""Share of the window's fit wall that the main thread spent in
``stream:next`` (``fit_timings_["covariance/next"]``: pulling the next chunk
from the dataset, reading it, and cutting or assembling the next device
batch on the host), in percent."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    reblock = ctx["load_module"]("work/reblock.py")
    return spans.phase_share_pct(ctx["fits"], reblock.NEXT_PHASE)
