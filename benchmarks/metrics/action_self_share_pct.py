"""Share of the window's fit wall that the Spark action took beside its
tasks (``fit_timings_["stage/action"]`` - ``["stage/task"]``: the span
``stage:action`` around the call that runs the executor tasks lazily and
collects their rows, less the tasks nested in it), in percent: scheduling
and the rows' way to the driver — Spark's share, read from the program's
own span where ``stage_collect_share_pct`` reads the stand-in's clock. None
where the program reports either key not."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    stage = ctx["load_module"]("work/stage.py")
    fits = ctx["fits"]
    wall = sum(f["wall"] for f in fits)
    action = [f["timings"].get(crossing.ACTION_PHASE) for f in fits]
    task = [f["timings"].get(stage.PHASES["task"]) for f in fits]
    if not wall or any(s is None for s in action + task):
        return None
    return 100.0 * (sum(action) - sum(task)) / wall
