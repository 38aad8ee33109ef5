"""The program's host spans, by name, and what a trace says under them.

``PROGRAM_SPANS`` are the names under which the streamed fit annotates
its own stages on the host plane of a profiler trace
(``spark_rapids_ml_tpu/ops/streaming.py``: ``STREAM_SPANS``;
``models/pca.py``: ``STREAMED_FIT_SPANS``), with the benchmark's own
``bench_fit`` around them; a test of the program holds the two lists
against each other. Everything else on the host plane belongs to the TPU
runtime. ``COARSE`` are the spans that only say "somewhere in the fit": an
idle second whose innermost program span is one of them is a second the
instrument cannot name.

Exact match, not substring: a renamed span reads ``None``, and the
program's test fails before that.
"""

from __future__ import annotations

import sys

BENCH_SPAN = "bench_fit"
COARSE = (BENCH_SPAN, "fit:pca", "streamed cov")
PROGRAM_SPANS = COARSE + (
    "stream:pass/mean", "stream:pass/gram", "stream:pass/stats",
    "stream:next", "stream:put",
    "stream:accumulate/mean", "stream:accumulate/pallas",
    "stream:accumulate/xla",
    "stream:sync/count", "stream:sync/cov",
    "xla eigh", "fit:fetch",
)
NO_SPAN = "(no host span)"  # xplane.idle_gaps' name for an uncovered gap
HOST_PREFIX = "/host:"


def program_planes(planes: list) -> list:
    """The trace with only the program's spans left on the host planes
    (device planes whole)."""
    keep = frozenset(PROGRAM_SPANS)
    out = []
    for plane in planes:
        if not plane["name"].startswith(HOST_PREFIX):
            out.append(plane)
            continue
        lines = [{"name": line["name"],
                  "events": [e for e in line["events"] if e[0] in keep]}
                 for line in plane["lines"]]
        out.append({"name": plane["name"],
                    "lines": [line for line in lines if line["events"]]})
    return out


def span_seconds(planes: list, name: str, lo: float = None,
                 hi: float = None) -> float:
    """Seconds of the host spans called ``name``, clipped to [lo, hi];
    None when the trace holds no such span."""
    total, seen = 0.0, False
    for plane in planes:
        if not plane["name"].startswith(HOST_PREFIX):
            continue
        for line in plane["lines"]:
            for n, start, dur in line["events"]:
                if n != name:
                    continue
                seen = True
                a = start if lo is None else max(start, lo)
                b = start + dur if hi is None else min(start + dur, hi)
                total += max(b - a, 0.0) / 1e9
    return total if seen else None


def idle_by_program_span(trace: dict, xplane) -> dict:
    """{span: chip-idle seconds of the traced window whose innermost
    program span it is}, every idle second once. With only the program's
    spans left on the host, ``xplane.idle_gaps``' "shortest span over the
    gap's middle" is the innermost of them. Kept on ``trace``: the three
    idle readers share one reduction."""
    if "idle_by_program_span" not in trace:
        planes = program_planes(trace["planes"])
        trace["program_planes"] = planes
        idle = dict(xplane.idle_gaps(
            planes, trace["lo"], trace["hi"], n=len(PROGRAM_SPANS) + 1))
        trace["idle_by_program_span"] = idle
        print("idle seconds by innermost program span: " + ", ".join(
            f"{name} {seconds:.3f}" for name, seconds in idle.items()),
            file=sys.stderr, flush=True)
    return trace["idle_by_program_span"]


def idle_share_pct(ctx: dict, names: tuple, require_span: bool = True):
    """Percent of the traced window's chip-idle seconds that fall under
    the spans ``names`` (innermost). None without a device trace, without
    idle time, or — ``require_span`` — where the trace holds none of the
    spans (a program that does not emit them)."""
    trace = ctx["trace"]
    if not trace or trace["busy_s"] is None:
        return None
    idle = idle_by_program_span(trace, ctx["load_module"]("xplane.py"))
    total = sum(idle.values())
    if not total:
        return None
    planes = trace["program_planes"]
    spans = {name: span_seconds(planes, name, trace["lo"], trace["hi"])
             for name in names}
    if require_span and all(s is None for s in spans.values()):
        return None
    for name in names:
        if spans[name] is not None:
            print(f"idle under {name!r}: {idle.get(name, 0.0):.3f}s of "
                  f"{total:.3f}s idle; the span itself lasts "
                  f"{spans[name]:.3f}s", file=sys.stderr, flush=True)
    return 100.0 * sum(idle.get(name, 0.0) for name in names) / total


def phase_share_pct(fits: list, key: str):
    """Percent of the window's fit wall spent under ``fit_timings_[key]``;
    None where a fit does not report the key."""
    wall = sum(f["wall"] for f in fits)
    seconds = [f["timings"].get(key) for f in fits]
    if not wall or any(s is None for s in seconds):
        return None
    return 100.0 * sum(seconds) / wall
