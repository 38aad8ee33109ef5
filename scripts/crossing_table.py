#!/usr/bin/env python3
"""When a fit's batches land on the chip, and when their steps start.

    python3 scripts/crossing_table.py <trace dir | recorded .json.gz> [--fits 3] [--json out.json]

Reads a profiler trace of back-to-back fits on ONE chip (a ``--trace 1`` run
of ``benchmarks/run.py`` leaves one under ``.bench_out/trace/<cell>``; the
two recorded traces under ``benchmarks/testdata`` are in the plain form) and
prints, per fit, seconds from the fit's start:

- ``put``: start of each ``stream:put`` span (the main thread's call);
- ``retile``: seconds of the batch's ``Linearize`` (the runtime's host-side
  re-tiling; long when several run at once);
- ``dispatch``: the ``H2D Dispatch`` right behind that ``Linearize`` — the
  batch joins the link's queue;
- ``landed``: the ``TransferToDevice=>IssueEvent=>Done`` of the batch — the
  link is FIFO, so the i-th landing is the i-th dispatch's;
- ``step``: device start of each accumulate program (``XLA Modules``), and
  the landing it sits behind.

This is the trace reading ``PERF.md`` §5 tabulates (ISSUE 32, step 0). It
reads what the TPU runtime names its own host events, which no test of the
program can hold: when a libtpu renames them the table comes out empty, it
does not raise.
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
STEPS = ("update_stats", "update_centered_gram", "update_mean_stats")
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
            "step": steps,
        })
    return out


def summary(tables: list) -> dict:
    """Medians over the fits: spacing of the landings, and for each step the
    landing it starts behind."""
    gaps = [b - a for t in tables for a, b in zip(t["landed"], t["landed"][1:])]
    n_steps = max((len(t["step"]) for t in tables), default=0)
    behind = []
    for i in range(n_steps):
        seen = [t["step"][i]["behind_landing"] for t in tables
                if len(t["step"]) > i]
        behind.append(statistics.median_low(seen))
    tail = [t["step"][-1]["start"] + t["step"][-1]["seconds"] - t["landed"][-1]
            for t in tables if t["step"] and t["landed"]]
    return {
        "fits": len(tables),
        "wall_median": statistics.median(t["wall"] for t in tables)
        if tables else None,
        "landing_gap_median": statistics.median(gaps) if gaps else None,
        "landing_gap_max": max(gaps) if gaps else None,
        "retile_median": statistics.median(
            r for t in tables for r in t["retile"]) if gaps else None,
        "step_behind_landing": behind,
        # device work left after the last landing: what no crossing hides
        "exposed_after_last_landing_median":
            statistics.median(tail) if tail else None,
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
