"""Tests of the crossing's readers (``work/crossing.py`` and their files
under ``metrics/``), on the CPU: hand-made planes and reports with known
answers, the recorded chip traces (one with landing spans, one without), a
program without the spans, the key or the door, and a tiny fit of every
cell whose line has to carry each reader the cell lists.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_crossing.py -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb  # noqa: E402 - the cells cut to the CPU's size
from test_sparkfit import stage_tiny_spec  # noqa: E402

from spark_rapids_ml_tpu.obs import report as report_module  # noqa: E402
from spark_rapids_ml_tpu.obs.report import FitReport  # noqa: E402

bench = tb.bench
S = 1e9  # ns a second
NEW_READERS = ("crossing_busy_pct", "idle_outside_crossing_pct",
               "crossing_gbytes_per_s", "bytes_put_per_fit_gb",
               "put_wait_share_pct", "staging_reused_pct",
               "landing_skew_pct", "action_self_share_pct")
COUNTER_READERS = ("crossing_gbytes_per_s", "bytes_put_per_fit_gb",
                   "put_wait_share_pct", "staging_reused_pct",
                   "landing_skew_pct")


def _read(name, ctx):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def _crossing():
    return bench.load_module("work/crossing.py")


# -- hand-made planes: two chips, a 10 s window --------------------------------
#
# chip 0 busy 2-3 s and 6-7 s: idle 0-2, 3-6, 7-10 (8 s). Its landings
# 0.5-1.5 and 1.0-2.5 (overlapping: one interval 0.5-2.5) and 3.5-5.0, so
# its idle seconds with no put outstanding are 0-0.5, 3-3.5, 5-6 and 7-10:
# 5.0 s. Chip 1 busy 4-5 s: idle 0-4 and 5-10 (9 s). Its landings -1-4 (cut
# at the window's start: the first idle gap lies wholly inside it) and
# 4.5-5.5: outside are 5.5-10, 4.5 s. Together 9.5 of 17 s.


def _planes(landings: bool = True) -> list:
    watchers = [
        {"name": "stream:landing/0", "events": [
            ["stream:landing/0", 0.5 * S, 1.0 * S],
            ["stream:landing/0", 1.0 * S, 1.5 * S],
            ["stream:landing/0", 3.5 * S, 1.5 * S]]},
        {"name": "stream:landing/1", "events": [
            ["stream:landing/1", -1.0 * S, 5.0 * S],
            ["stream:landing/1", 4.5 * S, 1.0 * S],
            # not a landing of a chip: left alone
            ["stream:landing/", 6.0 * S, 1.0 * S]]}] if landings else []
    host = {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [[bench.FIT_SPAN, 0.0, 10 * S],
                                    ["stream:put", 0.0, 3 * S]]},
        *watchers]}
    chips = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": [[f"%op.{j}", a * S, (b - a) * S]
                                       for j, (a, b) in enumerate(busy)]}]}
             for i, busy in enumerate([[(2, 3), (6, 7)], [(4, 5)]])]
    return [host, *chips]


def _trace(planes: list) -> dict:
    xplane = bench.load_module("xplane.py")
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    state = xplane.busy(planes, lo, hi)
    return {"planes": planes, "lo": lo, "hi": hi,
            "window_s": (hi - lo) / 1e9,
            "busy_s": state["busy_s"] if state["chips"] else None}


def test_landing_intervals_are_merged_per_chip_and_clipped():
    xplane = bench.load_module("xplane.py")
    got = _crossing().landing_intervals(_trace(_planes()), xplane)
    assert got == {0: [[0.5 * S, 2.5 * S], [3.5 * S, 5.0 * S]],
                   1: [[0.0, 4.0 * S], [4.5 * S, 5.5 * S]]}
    assert _crossing().landing_intervals(_trace(_planes(False)), xplane) == {}


def test_idle_outside_crossing_reads_every_chip():
    crossing = _crossing()
    chips = crossing.idle_outside_crossing(_trace(_planes()),
                                           bench.load_module("xplane.py"))
    assert sorted(chips) == [0, 1]
    idle0, outside0 = chips[0]
    assert idle0 == [[0.0, 2 * S], [3 * S, 6 * S], [7 * S, 10 * S]]
    assert outside0 == [[0.0, 0.5 * S], [3 * S, 3.5 * S], [5 * S, 6 * S],
                        [7 * S, 10 * S]]
    idle1, outside1 = chips[1]
    assert crossing.seconds(idle1) == pytest.approx(9.0)
    assert outside1 == [[5.5 * S, 10 * S]]
    ctx = {"fits": [], "load_module": bench.load_module,
           "trace": _trace(_planes())}
    assert _read("idle_outside_crossing_pct", ctx) == pytest.approx(
        100 * 9.5 / 17)


def test_cutting_holes_out_of_intervals():
    less = _crossing()._less
    assert less([[0, 10]], []) == [[0, 10]]
    assert less([[0, 10]], [[0, 10]]) == []
    assert less([[0, 10]], [[-5, 2], [4, 6], [9, 20]]) == [[2, 4], [6, 9]]
    assert less([[0, 2], [3, 5], [8, 9]], [[1, 4], [5, 8]]) == [
        [0, 1], [4, 5], [8, 9]]
    assert less([[0, 1], [2, 3]], [[5, 6]]) == [[0, 1], [2, 3]]


# -- hand-made reports: two fits of two chips ---------------------------------


def _timings(fit: int) -> dict:
    """A fit's own seconds: no two fits measure the same."""
    return {"covariance/crossing": 1.0 + 1e-6 * fit, "stage/action": 1.5,
            "stage/task": 1.2}


def _report(fit: int, per_chip: list, **ingest) -> FitReport:
    """The report of the window's fit number ``fit``: its phases hold the
    fit's timings (and more)."""
    batches = sum(chip.get("landings", 0) for chip in per_chip)
    return FitReport(algo="pca", trace_id="t", started_utc="",
                     wall_seconds=2.0, phases={**_timings(fit), "total": 2.0},
                     extra={"ingest": {"per_chip": per_chip,
                                       "batches": batches, **ingest}})


def _chip(device, crossing_seconds, last, **more) -> dict:
    return {"device": device, "bytes_put": 8_000_000_000,
            "crossing_seconds": crossing_seconds, "landings": 4,
            "last_landing_seconds": last, **more}


REPORTS = [
    _report(0, [_chip("TPU_0", 0.8, 1.0), _chip("TPU_1", 1.0, 1.2)],
            bytes_put=16_000_000_000, put_wait_seconds=0.5,
            staging_reused=3, staging_fresh=1),
    _report(1, [_chip("TPU_0", 0.8, 1.0), _chip("TPU_1", 1.0, 1.4)],
            bytes_put=16_000_000_000, put_wait_seconds=0.3,
            staging_reused=4, staging_fresh=0),
]


def _door(monkeypatch, reports: list) -> None:
    """The program's door, with ``reports`` in its ring."""
    monkeypatch.setattr(report_module, "recent_fit_reports",
                        lambda n=None, algo=None: list(reports))


def _ctx(fits: int = 2) -> dict:
    # (a deployment's stand-in adds a key of its own once the report is
    # made: ``stage/collect``)
    return {"fits": [{"wall": 2.0, "timings": {**_timings(fit),
                                               "stage/collect": 0.1}}
                     for fit in range(fits)],
            "load_module": bench.load_module, "trace": None}


def test_the_readers_on_hand_made_reports(monkeypatch):
    # the ring also holds an older fit's report, a report published inside
    # the window by something else (a nested fit: other seconds) and a
    # later one; the window's are told by their fits' own timings
    others = [_report(7, [], bytes_put=1), _report(8, [], bytes_put=2),
              _report(9, [], bytes_put=3)]
    _door(monkeypatch, [others[0], REPORTS[0], others[1], REPORTS[1],
                        others[2]])
    ctx = _ctx()
    assert [r["phases"] for r in _crossing().window_reports(ctx)] \
        == [r.phases for r in REPORTS]
    assert _read("crossing_busy_pct", ctx) == pytest.approx(50.0, rel=1e-5)
    # chip 0: 16 GB in 1.6 s, chip 1: 16 GB in 2.0 s
    assert _read("crossing_gbytes_per_s", ctx) == pytest.approx(9.0)
    assert _read("bytes_put_per_fit_gb", ctx) == pytest.approx(16.0)
    assert _read("put_wait_share_pct", ctx) == pytest.approx(20.0)
    assert _read("staging_reused_pct", ctx) == pytest.approx(87.5)
    # (1.2 - 1.0) / 2 and (1.4 - 1.0) / 2
    assert _read("landing_skew_pct", ctx) == pytest.approx(15.0)
    assert _read("action_self_share_pct", ctx) == pytest.approx(15.0)
    assert _read("idle_outside_crossing_pct", ctx) is None  # untraced


def test_one_chip_has_no_skew_and_whole_chunks_no_staging(monkeypatch):
    _door(monkeypatch, [
        _report(0, [_chip("TPU_0", 0.5, 0.7)], bytes_put=8_000_000_000,
                put_wait_seconds=0.0, staging_reused=0, staging_fresh=0)])
    ctx = _ctx(fits=1)
    assert _read("landing_skew_pct", ctx) is None
    assert _read("staging_reused_pct", ctx) is None
    assert _read("crossing_gbytes_per_s", ctx) == pytest.approx(16.0)
    assert _read("put_wait_share_pct", ctx) == 0.0


def test_every_reader_reads_nothing_on_a_program_without_them(monkeypatch):
    """The parent's package under this PR's ``benchmarks/``: no landing
    span in a trace, no ``covariance/crossing`` and no ``stage/action`` in
    ``fit_timings_``, no ``recent_fit_reports``. Nothing raises."""
    monkeypatch.delattr(report_module, "recent_fit_reports")
    xplane = bench.load_module("xplane.py")
    recorded = xplane.load_recorded(
        os.path.join(HERE, "testdata", "trace_v5e_spans.json.gz"))
    for trace in (None, _trace(_planes(landings=False)), _trace(recorded)):
        ctx = {"fits": [{"wall": 2.0, "timings": {"covariance": 1.0,
                                                  "stage/task": 0.5}}],
               "load_module": bench.load_module, "trace": trace}
        for name in NEW_READERS:
            assert _read(name, ctx) is None, name
    assert _crossing().window_reports(ctx) is None


def test_counter_readers_need_each_fits_own_report(monkeypatch):
    _door(monkeypatch, REPORTS[:1])  # the ring has lost a fit's report
    for name in COUNTER_READERS:
        assert _read(name, _ctx()) is None, name
    # two reports hold a fit's seconds: neither is taken for it
    _door(monkeypatch, REPORTS + REPORTS[:1])
    for name in COUNTER_READERS:
        assert _read(name, _ctx()) is None, name
    # a fit without timings cannot be told
    _door(monkeypatch, REPORTS)
    ctx = _ctx()
    ctx["fits"][0]["timings"] = {}
    assert _crossing().window_reports(ctx) is None
    # a fit that did not stream has no ``ingest``
    _door(monkeypatch, [REPORTS[0], FitReport(
        algo="pca", trace_id="t", started_utc="", wall_seconds=2.0,
        phases=_timings(1))])
    for name in COUNTER_READERS:
        assert _read(name, _ctx()) is None, name
    # a watcher that did not see every put of a fit land: its landing
    # counters are not final, and nothing is read from that window
    short = _report(1, [_chip("TPU_0", 0.8, 1.0)], bytes_put=8,
                    put_wait_seconds=0.1)
    short.extra["ingest"]["batches"] = 5
    _door(monkeypatch, [REPORTS[0], short])
    for name in COUNTER_READERS:
        assert _read(name, _ctx()) is None, name
    # a program with the door and without the landing counters (the ring
    # alone): the counts it has are read, the others are not
    old = [_report(0, [{"device": "TPU_0", "bytes_put": 8}], bytes_put=8,
                   put_wait_seconds=0.1)]
    _door(monkeypatch, old)
    ctx = _ctx(fits=1)
    assert _read("bytes_put_per_fit_gb", ctx) == pytest.approx(8e-9)
    assert _read("put_wait_share_pct", ctx) == pytest.approx(5.0)
    assert _read("crossing_gbytes_per_s", ctx) is None
    assert _read("staging_reused_pct", ctx) is None
    assert _read("landing_skew_pct", ctx) is None
    assert _crossing().window_reports({"fits": []}) is None


# -- the recorded fit with landing spans ---------------------------------------


@pytest.fixture(scope="module")
def landing_trace():
    xplane = bench.load_module("xplane.py")
    planes = xplane.load_recorded(
        os.path.join(HERE, "testdata", "trace_v5e_landing.json.gz"))
    return xplane, _trace(planes)


def test_the_recorded_fit_has_a_landing_a_put(landing_trace):
    """One fit of ``pca4096-fit-1pass`` on a v5e (my chip run, PR 38):
    four puts of 2 GiB, four landing spans on the chip's watcher's line,
    each ending a crossing's length after the one before."""
    xplane, trace = landing_trace
    crossing = _crossing()
    spans = sorted((s, d) for p in trace["planes"]
                   if p["name"].startswith(xplane.HOST_PREFIX)
                   for line in p["lines"] for n, s, d in line["events"]
                   if n == crossing.LANDING_PREFIX + "0")
    assert len(spans) == 4
    ends = [s + d for s, d in spans]
    gaps = [(b - a) / 1e9 for a, b in zip(ends, ends[1:])]
    two_gib_at_the_links_pace = 2 * 2 ** 30 / 14.19e9  # 0.1513 s
    assert gaps == pytest.approx([two_gib_at_the_links_pace] * 3, abs=5e-3)
    # a chip's landings are serial: none starts before the one before ends
    assert all(b[0] >= a[0] + a[1] - 1e3 for a, b in zip(spans, spans[1:]))
    (merged,) = crossing.landing_intervals(trace, xplane).values()
    assert crossing.seconds(merged) == pytest.approx(
        sum(d for _, d in spans) / 1e9, rel=1e-6)


def test_the_recorded_fits_idle_seconds_lie_under_its_landings(
        landing_trace):
    xplane, trace = landing_trace
    ctx = {"fits": [], "load_module": bench.load_module, "trace": trace}
    share = _read("idle_outside_crossing_pct", ctx)
    (idle, outside), = _crossing().idle_outside_crossing(
        trace, xplane).values()
    assert share == pytest.approx(
        100 * _crossing().seconds(outside) / _crossing().seconds(idle))
    # whole chunks: the chip idles while a put is outstanding, but for the
    # solve's and the fetch's gaps and the head before the first put returns
    assert 0.0 < share < 25.0


# -- the entries and a tiny fit of every cell ---------------------------------


def test_every_new_metric_is_listed_with_its_reader():
    listed = {m["name"]: m for m in tb.BENCHMARK["per_layer"]}
    for name in NEW_READERS:
        assert name in listed, name
        assert callable(bench.load_module(f"metrics/{name}.py").read)
        assert set(listed[name].get("workloads", tb.CELLS)) <= set(tb.CELLS)
    layers = {m["layer"] for m in tb.BENCHMARK["per_layer"]
              if m["name"] not in NEW_READERS}
    assert {listed[name]["layer"] for name in NEW_READERS} <= layers


@pytest.mark.parametrize("cell", tb.CELLS)
def test_a_traced_style_line_carries_every_reader_the_cell_lists(
        monkeypatch, cell):
    """A tiny fit of the cell through the program as it is: every new
    reader the cell lists that reads ``fit_timings_`` or the reports finds
    what it reads (none is ``null`` for want of a key), and the program's
    own count of the bytes is the rows' size."""
    spec = stage_tiny_spec(cell) if "recordBatchRows" in tb.tiny_spec(
        cell)["config"]["params"] else tb.tiny_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        3, config["n_features"], rows, n_chunks, config["rows"])
    new_dataset = bench.dataset_factory(spec["traffic"], chunks)
    fits = [bench.fit_once(config, new_dataset) for _ in range(2)]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    listed = {m["name"] for m in spec["per_layer"]}
    chips = spec["cell"]["chips"]
    for name in NEW_READERS:
        if name == "idle_outside_crossing_pct":  # the device trace's
            continue
        value = _read(name, ctx)
        if name in listed:
            assert value is not None, name
    import jax

    # the front's tasks put in the device's dtype: float64 where a session
    # that also holds ``tests/`` (its conftest) has switched x64 on
    front = config["estimator"].endswith("SparkStagePCA")
    itemsize = 8 if front and jax.config.jax_enable_x64 else 4
    nbytes = rows * n_chunks * config["n_features"] * itemsize
    crossings = 1
    if spec["traffic"]["input_form"] == "callable":
        # nothing is kept on the CPU: every pass puts the rows, and the
        # fit says how many it made (one where the shifted Gram stands)
        ingest = _crossing().window_ingest(ctx)
        assert all(i["batches_kept"] == 0 for i in ingest)
        crossings = sum(i["passes"] for i in ingest) / len(ingest)
    assert _read("bytes_put_per_fit_gb", ctx) == pytest.approx(
        crossings * nbytes / 1e9)
    # and the phase's rate reads the program's bytes, not the static count
    seconds = sum(f["timings"]["covariance"] for f in fits)
    assert _read("covariance_gbytes_per_s", dict(
        ctx, bytes_put_per_fit=-1.0)) == pytest.approx(
        crossings * nbytes * len(fits) / seconds / 1e9)
    assert 0.0 < _read("crossing_busy_pct", ctx) <= 100.0
    assert (_read("landing_skew_pct", ctx) is not None) == (chips > 1)
