"""From a profiler trace to numbers: the benchmark's one trace reduction.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
data — planes, their lines, and events as ``[name, start_ns, duration_ns]``
— with nothing but JAX. Everything else works on that plain data, so
``testdata/trace_v5e.json.gz`` (a recorded chip trace in the same form)
checks it without a chip.

On a TPU v5e as jax 0.9.0 traces it (looked at by hand, PR 25): each chip
is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per
executed HLO op (a Pallas kernel is one op), ``XLA Modules`` one event per
executed program, named ``jit_<function>(<fingerprint>)``; host threads are
lines of the plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation``
spans appear under their own names.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(xplane_path: str) -> list:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(xplane_path)
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [[e.name, float(e.start_ns),
                                    float(e.duration_ns)]
                                   for e in line.events]}
                       for line in plane.lines]}
            for plane in data.planes]


def load_recorded(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(planes: list) -> list:
    chips = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)
             and p["name"][len(DEVICE_PREFIX):].isdigit()]
    return sorted(chips, key=lambda p: int(p["name"][len(DEVICE_PREFIX):]))


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def union_seconds(events: list, lo: float = None, hi: float = None):
    """(seconds covered, merged [start, end] intervals) of the events,
    clipped to [lo, hi] where given."""
    spans = []
    for _, start, dur in events:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) / 1e9, merged


def window(planes: list, span_name: str):
    """[lo, hi] in ns: from the start of the first host span called
    ``span_name`` to the end of the last, or None when there is none."""
    found = [(s, s + d) for p in planes if p["name"].startswith(HOST_PREFIX)
             for line in p["lines"] for n, s, d in line["events"]
             if n == span_name]
    if not found:
        return None
    return min(a for a, _ in found), max(b for _, b in found)


def busy(planes: list, lo: float = None, hi: float = None) -> dict:
    """Seconds in which an op ran, averaged over the chips traced, with
    each chip's merged busy intervals."""
    chips = device_planes(planes)
    per_chip = [union_seconds(_line(p, OPS_LINE) or _line(p, MODULES_LINE),
                              lo, hi) for p in chips]
    if not per_chip:
        return {"busy_s": 0.0, "chips": 0, "intervals": []}
    return {"busy_s": sum(s for s, _ in per_chip) / len(per_chip),
            "chips": len(per_chip), "intervals": [iv for _, iv in per_chip]}


def program_seconds(planes: list, names: tuple, lo: float = None,
                    hi: float = None) -> dict:
    """Device seconds of the executed programs whose traced name holds one
    of ``names``, summed over the chips: {traced name: seconds}."""
    out: dict = {}
    for plane in device_planes(planes):
        for name, start, dur in _line(plane, MODULES_LINE):
            if lo is not None and (start + dur <= lo or start >= hi):
                continue
            if any(n in name for n in names):
                key = name.split("(")[0]
                out[key] = out.get(key, 0.0) + dur / 1e9
    return out


def top_device_ops(planes: list, lo: float = None, hi: float = None,
                   n: int = 10) -> list:
    totals: dict = {}
    for plane in device_planes(planes):
        for name, start, dur in (_line(plane, OPS_LINE)
                                 or _line(plane, MODULES_LINE)):
            if lo is not None and (start + dur <= lo or start >= hi):
                continue
            op = name.split(" = ")[0]  # an op's name is its whole HLO line
            totals[op] = totals.get(op, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(planes: list, lo: float, hi: float, n: int = 10) -> list:
    """The idle time of the first chip inside [lo, hi], by what the host
    was doing: each gap between busy intervals goes to the shortest host
    span that covers its middle. [[host span, seconds], ...], largest
    first."""
    state = busy(planes, lo, hi)
    if not state["intervals"]:
        return []
    edges = [lo] + [t for iv in state["intervals"][0] for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    mids = [(a + b) / 2 for a, b in gaps]  # ascending, as the gaps are
    host = sorted((d, name, s) for p in planes
                  if p["name"].startswith(HOST_PREFIX)
                  for line in p["lines"] for name, s, d in line["events"]
                  if d > 0)
    # shortest span first; each takes the middles it covers that no shorter
    # span took. skip[i] points past the middles already taken (union-find),
    # so the whole is near linear in spans + gaps: a 30 s trace has 1e5 of
    # each, and gaps x spans in Python ran for ten minutes.
    owner = [None] * len(mids)
    skip = list(range(len(mids) + 1))

    def free(i: int) -> int:
        while skip[i] != i:
            skip[i] = skip[skip[i]]
            i = skip[i]
        return i

    for d, name, s in host:
        i = free(bisect.bisect_left(mids, s))
        while i < len(mids) and mids[i] <= s + d:
            owner[i] = name
            skip[i] = i + 1
            i = free(i + 1)
    totals: dict = {}
    for (a, b), name in zip(gaps, owner):
        name = name or "(no host span)"
        totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summary(planes: list, per_plane: int = 12) -> str:
    """What a person looks at first: planes, lines, and the longest-running
    event names of each line."""
    out = []
    for plane in planes:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            totals: dict = {}
            for name, _, dur in line["events"]:
                t = totals.setdefault(name, [0, 0.0])
                t[0] += 1
                t[1] += dur / 1e9
            out.append(f"  LINE {line['name']} events={len(line['events'])}")
            for name, (cnt, sec) in sorted(
                    totals.items(), key=lambda kv: -kv[1][1])[:per_plane]:
                out.append(f"    {sec:10.6f}s x{cnt:<6d} {name[:120]}")
    return "\n".join(out)
