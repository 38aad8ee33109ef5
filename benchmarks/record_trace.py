#!/usr/bin/env python3
"""Look at a trace by hand, and cut a small recorded one for the tests.

    python3 benchmarks/record_trace.py <trace dir> [--out file.json.gz] [--fits 1]

Prints ``xplane.summary`` of the newest ``.xplane.pb`` under the directory
(planes, lines, the event names that took most time). With ``--out`` it
also writes the trace's first ``--fits`` ``bench_fit`` spans in the plain
form ``xplane.load`` gives — device planes whole, host lines without
events shorter than 20 us — which is how ``testdata/trace_v5e.json.gz``
was made from a run of ``pca4096-fit-2pass`` on the chip.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import xplane  # noqa: E402
from run import FIT_SPAN  # noqa: E402


def cut(planes: list, fits: int) -> list:
    spans = sorted((s, s + d) for p in planes
                   if p["name"].startswith(xplane.HOST_PREFIX)
                   for line in p["lines"] for n, s, d in line["events"]
                   if n == FIT_SPAN)[:fits]
    lo, hi = spans[0][0], spans[-1][1]
    out = []
    for plane in planes:
        host = plane["name"].startswith(xplane.HOST_PREFIX)
        if not host and not plane["name"].startswith(xplane.DEVICE_PREFIX):
            continue
        lines = []
        for line in plane["lines"]:
            events = [[n, s - lo, d] for n, s, d in line["events"]
                      if s + d > lo and s < hi and (not host or d >= 20e3)]
            if events:
                lines.append({"name": line["name"], "events": events})
        out.append({"name": plane["name"], "lines": lines})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("--out")
    p.add_argument("--fits", type=int, default=1)
    args = p.parse_args(argv)
    planes = xplane.load(xplane.find_xplane(args.trace_dir))
    print(xplane.summary(planes))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(cut(planes, args.fits), f, separators=(",", ":"))
        print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
