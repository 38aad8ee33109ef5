"""How far apart the chips' last landings of a fit lie: (the latest chip's
``last_landing_seconds`` - the earliest's) over the fit's wall, median of
the window's fits, in percent. The chips that finish early wait for the
last at the all-reduce. None on one chip, or where the program has no door
for its reports or no landing counters."""

import statistics


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    skews = []
    for fit, counted in zip(ctx["fits"], ingest):
        last = [chip.get("last_landing_seconds")
                for chip in counted.get("per_chip", ())]
        if len(last) < 2 or any(t is None for t in last) or not fit["wall"]:
            return None
        skews.append(100.0 * (max(last) - min(last)) / fit["wall"])
    return statistics.median(skews) if skews else None
