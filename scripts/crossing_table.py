#!/usr/bin/env python3
"""When a fit's batches land on the chip, and when their steps start.

    python3 scripts/crossing_table.py <trace dir | recorded .json.gz> [--fits 3] [--json out.json]

Reads a profiler trace of back-to-back fits (a ``--trace 1`` run of
``benchmarks/run.py`` leaves one under ``.bench_out/trace/<cell>``; the
recorded traces under ``benchmarks/testdata`` are in the plain form) and
prints, per fit, seconds from the fit's start:

- ``put``: start of each ``stream:put`` span (the main thread's call);
- ``retile``: seconds of the batch's ``Linearize`` (the runtime's host-side
  re-tiling; long when several run at once);
- ``dispatch``: the ``H2D Dispatch`` right behind that ``Linearize`` — the
  batch joins the link's queue;
- ``landed``: the end of each of the program's own landing spans
  (``stream:landing/<device id>``, PR 38: one a put, on its chip's watcher's
  line), **per chip** under ``landed_by_chip`` with each span's start under
  ``outstanding_by_chip``; ``landed`` itself is the first chip's, the chip
  whose steps are tabulated. Where the trace has no such span (a program
  before PR 38: the two older recorded traces) it falls back to the
  runtime's ``TransferToDevice=>IssueEvent=>Done`` of the batch — the link
  is FIFO, so the i-th landing is the i-th dispatch's; those events do not
  name their chip, so that reading is one chip's only. ``landed_runtime``
  keeps the runtime's reading beside the program's (one chip);
- ``step``: device start of each accumulate program (``XLA Modules``; and of
  ``recentre_gram``, which ends a two-pass fit's covariance phase), and the
  landing it sits behind.

This is the trace reading ``PERF.md`` §5 tabulates (ISSUE 32, step 0). But
for ``landed`` it reads what the TPU runtime names its own host events, which
no test of the program can hold: when a libtpu renames them those columns
come out empty, they do not raise.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import xplane  # noqa: E402

FIT_SPAN = "fit:pca"
PUT_SPAN = "stream:put"
RETILE = "Linearize"
DISPATCH = "H2D Dispatch"
LANDED = "tpu::System::TransferToDevice=>IssueEvent=>Done"
LANDING_PREFIX = "stream:landing/"  # + the device id: the program's own
# the accumulate programs and, since PR 39, the two-pass fit's re-centring
# of its shifted Gram: the last device work of its covariance phase
STEPS = ("update_stats", "update_centered_gram", "update_mean_stats",
         "recentre_gram")
BIG_RETILE_NS = 20e6  # a batch's re-tiling; a scalar's or a mask's is µs
SAME_LANDING_NS = 2e6  # Done events this close are one landing


def _host_events(planes: list) -> list:
    """(line name, [name, start, dur]) of every host event."""
    return [(line["name"], e) for p in planes
            if p["name"].startswith(xplane.HOST_PREFIX)
            for line in p["lines"] for e in line["events"]]


def fit_tables(planes: list) -> list:
    """One dict per ``fit:pca`` span of the trace, times in seconds from the
    fit's start."""
    host = _host_events(planes)
    fits = sorted((s, s + d) for _, (n, s, d) in host if n == FIT_SPAN)
    chips = xplane.device_planes(planes)
    modules = sorted(
        (s, d, n.split("(")[0]) for n, s, d in
        (xplane._line(chips[0], xplane.MODULES_LINE) if chips else [])
        if any(k in n for k in STEPS))
    by_line: dict = {}  # a thread's re-tilings and dispatches, in time order
    for line, (n, s, d) in host:
        if n in (RETILE, DISPATCH):
            by_line.setdefault(line, []).append((s, d, n))
    for events in by_line.values():
        events.sort()
    done = sorted(s for _, (n, s, _) in host if n == LANDED)
    puts = sorted(s for _, (n, s, _) in host if n == PUT_SPAN)
    landing_spans: dict = {}  # device id -> [(start, end)], in time order
    for _, (n, s, d) in host:
        chip = n[len(LANDING_PREFIX):]
        if n.startswith(LANDING_PREFIX) and chip.isdigit():
            landing_spans.setdefault(int(chip), []).append((s, s + d))
    first_chip = (int(chips[0]["name"][len(xplane.DEVICE_PREFIX):])
                  if chips else None)
    out = []
    for lo, hi in fits:
        batches = []  # (retile start, retile seconds, dispatch start)
        for events in by_line.values():
            for i, (s, d, n) in enumerate(events):
                if n == RETILE and d >= BIG_RETILE_NS and lo <= s < hi:
                    nxt = next((e[0] for e in events[i + 1:]
                                if e[2] == DISPATCH and e[0] >= s + d - 1e3),
                               None)
                    if nxt is not None:
                        batches.append((s, d, nxt))
        batches.sort(key=lambda b: b[2])  # the link's order: by dispatch
        landed = []
        if batches:
            for t in done:
                if t < batches[0][2] or t >= hi or len(landed) == len(batches):
                    continue
                # a landing cannot precede its own dispatch
                if t < batches[len(landed)][2]:
                    continue
                if landed and t - landed[-1] < SAME_LANDING_NS:
                    continue
                landed.append(t)
        runtime = landed
        # a landing belongs to the fit its span ended in
        by_chip = {}
        for chip, spans in sorted(landing_spans.items()):
            ended_here = sorted((a, b) for a, b in spans if lo <= b < hi)
            if ended_here:
                by_chip[chip] = ended_here
        if by_chip:
            mine = by_chip.get(first_chip) or next(iter(by_chip.values()))
            landed = [b for _, b in mine]
        steps = []
        for s, d, name in modules:
            if lo <= s < hi:
                behind = sum(1 for t in landed if t <= s + 5e6)
                steps.append({"program": name, "start": (s - lo) / 1e9,
                              "seconds": d / 1e9, "behind_landing": behind})
        out.append({
            "wall": (hi - lo) / 1e9,
            "put": [(t - lo) / 1e9 for t in puts if lo <= t < hi],
            "retile": [b[1] / 1e9 for b in batches],
            "dispatch": [(b[2] - lo) / 1e9 for b in batches],
            "landed": [(t - lo) / 1e9 for t in landed],
            "landed_from": "program" if by_chip else "runtime",
            "landed_runtime": [(t - lo) / 1e9 for t in runtime],
            "landed_by_chip": {chip: [(b - lo) / 1e9 for _, b in spans]
                               for chip, spans in by_chip.items()},
            "outstanding_by_chip": {chip: [(a - lo) / 1e9 for a, _ in spans]
                                    for chip, spans in by_chip.items()},
            "step": steps,
        })
    return out


def summary(tables: list) -> dict:
    """Medians over the fits: spacing of the landings, and for each step the
    landing it starts behind."""
    gaps = [b - a for t in tables for a, b in zip(t["landed"], t["landed"][1:])]
    retiles = [r for t in tables for r in t["retile"]]
    n_steps = max((len(t["step"]) for t in tables), default=0)
    behind = []
    for i in range(n_steps):
        seen = [t["step"][i]["behind_landing"] for t in tables
                if len(t["step"]) > i]
        behind.append(statistics.median_low(seen))
    tail = [t["step"][-1]["start"] + t["step"][-1]["seconds"] - t["landed"][-1]
            for t in tables if t["step"] and t["landed"]]
    # the program's landing against the runtime's, where a fit has both and
    # as many of one as of the other (one chip): the watcher's wake-up
    late = [a - b for t in tables if t["landed_from"] == "program"
            and len(t["landed"]) == len(t["landed_runtime"])
            for a, b in zip(t["landed"], t["landed_runtime"])]
    chips = sorted({chip for t in tables for chip in t["landed_by_chip"]})
    by_chip = {}
    for chip in chips:
        mine = [t for t in tables if t["landed_by_chip"].get(chip)]
        chip_gaps = [b - a for t in mine for a, b in zip(
            t["landed_by_chip"][chip], t["landed_by_chip"][chip][1:])]
        # a span starts at max(its put returned, the chip's landing before)
        spans = [b - a for t in mine for a, b in zip(
            t["outstanding_by_chip"][chip], t["landed_by_chip"][chip])]
        by_chip[chip] = {
            "landings_median": statistics.median(
                len(t["landed_by_chip"][chip]) for t in mine),
            "first_landing_median": statistics.median(
                t["landed_by_chip"][chip][0] for t in mine),
            "last_landing_median": statistics.median(
                t["landed_by_chip"][chip][-1] for t in mine),
            "landing_gap_median": statistics.median(chip_gaps)
            if chip_gaps else None,
            "outstanding_seconds_median": statistics.median(
                sum(b - a for a, b in zip(t["outstanding_by_chip"][chip],
                                          t["landed_by_chip"][chip]))
                for t in mine),
            "span_seconds_median": statistics.median(spans),
        }
    return {
        "fits": len(tables),
        "wall_median": statistics.median(t["wall"] for t in tables)
        if tables else None,
        "landing_gap_median": statistics.median(gaps) if gaps else None,
        "landing_gap_max": max(gaps) if gaps else None,
        "retile_median": statistics.median(retiles) if retiles else None,
        "step_behind_landing": behind,
        # device work left after the last landing: what no crossing hides
        "exposed_after_last_landing_median":
            statistics.median(tail) if tail else None,
        "landed_from": sorted({t["landed_from"] for t in tables}),
        "program_minus_runtime_landing_median":
            statistics.median(late) if late else None,
        "program_minus_runtime_landing_max":
            max(late, key=abs) if late else None,
        "by_chip": by_chip,
    }


def _fmt(values: list) -> str:
    return " ".join(f"{v:.4f}" for v in values)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace")
    p.add_argument("--fits", type=int, default=3,
                   help="fits to print in full (the summary reads all)")
    p.add_argument("--json", help="write every fit's table and the summary")
    args = p.parse_args(argv)
    if os.path.isdir(args.trace):
        planes = xplane.load(xplane.find_xplane(args.trace))
    else:
        planes = xplane.load_recorded(args.trace)
    tables = fit_tables(planes)
    for i, t in enumerate(tables[:args.fits]):
        print(f"fit {i}: wall {t['wall']:.4f}s")
        for key in ("put", "retile", "dispatch", "landed"):
            print(f"  {key:9s}{_fmt(t[key])}")
        if t["landed_from"] == "program":
            print(f"  runtime  {_fmt(t['landed_runtime'])}")
            for chip, landed in t["landed_by_chip"].items():
                print(f"  chip {chip}   outstanding from "
                      f"{_fmt(t['outstanding_by_chip'][chip])} landed "
                      f"{_fmt(landed)}")
        for s in t["step"]:
            print(f"  step     {s['start']:.4f} +{s['seconds']:.4f} "
                  f"{s['program']} behind landing {s['behind_landing']}")
    medians = summary(tables)
    print(json.dumps(medians))
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"summary": medians, "fits": tables}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
