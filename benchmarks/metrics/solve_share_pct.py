"""Share of the window's fit wall spent in the estimator's ``solve`` phase
(``fit_timings_["solve"]``: the eigensolve and its fetch), in percent."""


def read(ctx):
    wall = sum(f["wall"] for f in ctx["fits"])
    solve = [f["timings"].get("solve") for f in ctx["fits"]]
    if not wall or any(s is None for s in solve):
        return None
    return 100.0 * sum(solve) / wall
