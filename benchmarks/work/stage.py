"""The Spark front's stage, by name: what ``spark.PCA(...).fit(frame)`` adds
around the one streamed loop.

A fit of the front is a stage of executor tasks and a driver half
(``spark_rapids_ml_tpu/spark/estimator.py`` ``PCA._fit``). ``SPANS`` are the
host spans the program emits (``spark/device_aggregate.py``: ``SPAN_TASK``,
``SPAN_HANDBACK``; ``spark/estimator.py``: ``SPAN_MERGE``):

* ``task``: one executor task — a partition's record batches through the
  one loop (the ``stream:*`` spans of ``work/spans.py`` inside it) and its
  hand-back;
* ``handback``: inside a task, the Gram's device-to-host fetch, its float64
  form and the stats row's Arrow batch, exactly that;
* ``merge``: on the driver, reading the collected rows, the float64 sum of
  the partitions' moments and the centring ``(G - N mu mu^T) / (N - 1)``.

``PHASES`` are the ``fit_timings_`` keys their seconds are summed under
(the tasks' where executor and driver are one process). ``COLLECT_PHASE`` is
not the program's: the stand-in for Spark's hand-over
(``deploy/spark_stage.py``) times its own Arrow IPC round trip of every
stats row and adds it to the fit's timings under that key. A test of the
program holds the program's names against what it emits.

The names are deliberately NOT in ``work/spans.py``'s ``PROGRAM_SPANS``
(which may not be edited here): the accepted idle readers give a chip-idle
second under the hand-back or the merge to the enclosing coarse span
(``idle_unattributed_pct``). ``idle_share_pct`` below reads the same
reduction with the stage's spans kept as well.

No roofline: the stage adds no kernel. The Gram work is ``work/gram.py``'s.
"""

from __future__ import annotations

import sys

SPANS = {"task": "stage:task", "handback": "stage:handback",
         "merge": "stage:merge"}
PHASES = {"task": "stage/task", "handback": "stage/handback",
          "merge": "stage/merge"}
COLLECT_PHASE = "stage/collect"


def idle_share_pct(ctx: dict, parts: tuple):
    """Percent of the traced window's chip-idle seconds whose innermost
    span — of the program's listed spans and the stage's — is one of
    ``SPANS[part]``. None without a device trace, without idle time, or
    where the trace holds no span of the stage (a program without one)."""
    trace = ctx["trace"]
    if not trace or trace["busy_s"] is None:
        return None
    spans = ctx["load_module"]("work/spans.py")
    keep = frozenset(spans.PROGRAM_SPANS + tuple(SPANS.values()))
    planes, seen = [], False
    for plane in trace["planes"]:
        if plane["name"].startswith(spans.HOST_PREFIX):
            lines = [{"name": line["name"],
                      "events": [e for e in line["events"] if e[0] in keep]}
                     for line in plane["lines"]]
            seen = seen or any(e[0] in SPANS.values()
                               for line in lines for e in line["events"])
            plane = {"name": plane["name"],
                     "lines": [line for line in lines if line["events"]]}
        planes.append(plane)
    if not seen:
        return None
    idle = dict(ctx["load_module"]("xplane.py").idle_gaps(
        planes, trace["lo"], trace["hi"], n=len(keep) + 1))
    total = sum(idle.values())
    if not total:
        return None
    print("idle seconds by innermost span, the stage's kept: " + ", ".join(
        f"{name} {seconds:.3f}" for name, seconds in idle.items()),
        file=sys.stderr, flush=True)
    return 100.0 * sum(idle.get(SPANS[p], 0.0) for p in parts) / total
