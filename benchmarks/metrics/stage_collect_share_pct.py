"""Share of the window's fit wall that the stats rows spent crossing from
the executor tasks to the driver: the stand-in's Arrow IPC round trip of
every row (``deploy/spark_stage.py``, on the host's clock, added to the
fit's timings as ``stage/collect``), in percent. Spark's share of the fit,
not the program's; kept apart from ``handback_share_pct`` and
``merge_share_pct`` so that those stay the program's own. None where the
estimator reports no such key."""


def read(ctx):
    spans = ctx["load_module"]("work/spans.py")
    stage = ctx["load_module"]("work/stage.py")
    return spans.phase_share_pct(ctx["fits"], stage.COLLECT_PHASE)
