"""The host-to-device crossing as the program itself sees it: every put's
landing a span on its own chip's line, and what a fit counted, out through
the program's one door.

The accepted idle readers (``work/spans.py``) say where the MAIN thread
stood over each chip-idle gap; none says whether a transfer was outstanding
meanwhile. Since PR 38 the program has, per device, a landing watcher
(``spark_rapids_ml_tpu/ops/streaming.py`` ``_Watcher``): one host span a
put, ``stream:landing/<device id>``, on the watcher's own host line, from
max(the put's ``device_put`` returned, the chip's previous landing) to the
landing. The union of a chip's landing spans is the time during which at
least one put of that chip was outstanding — the runtime's re-tiling, the
wait in the link's queue and the crossing — and ``<device id>`` pairs the
line with the device plane ``/device:TPU:<id>``.

* ``LANDING_PREFIX``: the span's name up to the id (``SPAN_LANDING`` + "/");
* ``CROSSING_PHASE``: the ``fit_timings_`` key that holds the fit's fullest
  chip's ``crossing_seconds`` (it overlaps the main thread's phases);
* ``LANDING_COUNTERS``: per chip in ``extra["ingest"]["per_chip"][]``
  (the fullest chip's ``crossing_seconds`` in ``extra["ingest"]`` too);
* ``ACTION_SPAN`` / ``ACTION_PHASE``: the Spark front's action
  (``spark/estimator.py``: the call that runs the executor tasks lazily and
  collects their rows); ``work/stage.py``'s ``PHASES["task"]`` nests in it,
  so the difference is Spark's share of the fit.

A test of the program (``tests/test_streaming_spans.py``) holds every name
against what the program emits. None of them is in ``work/spans.py``'s
``PROGRAM_SPANS`` or ``work/stage.py``'s ``SPANS``, so every accepted reader
reads what it read.

``window_reports`` is the door for counters: the program keeps the newest
fit reports of the process (``obs/report.py`` ``recent_fit_reports``), and
a fit's report is the one whose ``phases`` hold the fit's own
``fit_timings_``, second for second — whatever else published a report
meanwhile. Every reader returns ``None`` where its span, key or counter is
missing — a program without them (the parent) prints nothing, not a
zero.

No roofline and no peak: ``peaks.json`` has no sourced figure for the host
link.
"""

from __future__ import annotations

LANDING_PREFIX = "stream:landing/"
CROSSING_PHASE = "covariance/crossing"
LANDING_COUNTERS = ("landings", "crossing_seconds", "last_landing_seconds")
ACTION_SPAN = "stage:action"
ACTION_PHASE = "stage/action"


def landing_intervals(trace: dict, xplane) -> dict:
    """{device id: merged [start, end] ns intervals, clipped to the traced
    window, during which a put of that chip was outstanding} from the host
    planes' landing spans; {} where the trace has none."""
    found: dict = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(xplane.HOST_PREFIX):
            continue
        for line in plane["lines"]:
            for event in line["events"]:
                chip = event[0][len(LANDING_PREFIX):]
                if event[0].startswith(LANDING_PREFIX) and chip.isdigit():
                    found.setdefault(int(chip), []).append(event)
    merged = {chip: xplane.union_seconds(events, trace["lo"], trace["hi"])[1]
              for chip, events in found.items()}
    return {chip: spans for chip, spans in merged.items() if spans}


def _less(intervals: list, holes: list) -> list:
    """``intervals`` with ``holes`` cut out; both sorted and disjoint."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        i = j
        while a < b and i < len(holes) and holes[i][0] < b:
            if holes[i][0] > a:
                out.append([a, holes[i][0]])
            a = max(a, holes[i][1])
            i += 1
        if a < b:
            out.append([a, b])
    return out


def idle_outside_crossing(trace: dict, xplane) -> dict:
    """{device id: (idle intervals of the chip in the traced window, those
    of them during which no put of that chip was outstanding)} for EVERY
    traced chip; None without a device trace or where the trace holds no
    landing span."""
    landings = landing_intervals(trace, xplane)
    if trace["busy_s"] is None or not landings:
        return None
    lo, hi = trace["lo"], trace["hi"]
    state = xplane.busy(trace["planes"], lo, hi)
    out = {}
    for plane, busy in zip(xplane.device_planes(trace["planes"]),
                           state["intervals"]):
        chip = int(plane["name"][len(xplane.DEVICE_PREFIX):])
        idle = _less([[lo, hi]], busy)
        out[chip] = (idle, _less(idle, landings.get(chip, [])))
    return out


def seconds(intervals: list) -> float:
    return sum(b - a for a, b in intervals) / 1e9


def _is_the_fits(report, timings: dict) -> bool:
    """Whether ``report`` is of the fit that measured ``timings``: the
    phases both hold (a deployment's stand-in may add a key of its own to
    ``fit_timings_`` once the report is made) are equal second for second,
    and one of them is not 0."""
    shared = [phase for phase in timings if phase in report.phases]
    return any(timings[phase] for phase in shared) and all(
        report.phases[phase] == timings[phase] for phase in shared)


def window_reports(ctx: dict):
    """The program's report of each of the window's fits, in the fits'
    order (dicts, as ``FitReport.as_dict`` gives them). A fit's report is
    the one report of the ring whose ``phases`` hold the seconds of the
    fit's ``timings`` (the model's ``fit_timings_`` are merged into its
    report as they are): a nested fit's report, a warm-up's or the
    reference's cannot be taken for it. None where the program has no
    ``recent_fit_reports``, where the window is empty, or where a fit has
    no such report (no timings; the ring holds the newest 256) or more
    than one."""
    try:
        from spark_rapids_ml_tpu.obs.report import recent_fit_reports
    except ImportError:
        return None
    reports = recent_fit_reports()
    paired = []
    for fit in ctx["fits"]:
        own = [r for r in reports if _is_the_fits(r, fit["timings"])]
        if len(own) != 1:
            return None
        paired.append(own[0].as_dict())
    return paired or None


def window_ingest(ctx: dict):
    """``extra["ingest"]`` of each of the window's fits; None where a fit
    has none (no door, or a fit that did not stream), or where a program
    that counts landings did not see every put of a fit land (its landing
    counters would not be final)."""
    reports = window_reports(ctx)
    if reports is None:
        return None
    ingest = [r.get("extra", {}).get("ingest") for r in reports]
    if any(i is None for i in ingest):
        return None
    for fit in ingest:
        landings = [chip.get("landings") for chip in fit.get("per_chip", ())]
        if landings and None not in landings \
                and sum(landings) != fit.get("batches"):
            return None
    return ingest


def counter_sum(ingest: list, key: str):
    """Sum of the counter ``key`` over the fits; None where one lacks it."""
    values = [i.get(key) for i in ingest]
    return None if any(v is None for v in values) else sum(values)
