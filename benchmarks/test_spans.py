"""Tests of the six span readers (``work/spans.py`` and their files under
``metrics/``), on the CPU: a synthetic trace with known answers, the two
recorded chip traces, and one traced run at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb  # noqa: E402 - the cells cut to the CPU's size

bench = tb.bench
IDLE_READERS = ("idle_in_put_pct", "idle_in_sync_pct", "idle_unattributed_pct")
SHARE_READERS = ("put_share_pct", "sync_share_pct", "fetch_share_pct")


def read(name: str, ctx: dict):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def trace_ctx(planes: list, fits: list = ()) -> dict:
    xplane = bench.load_module("xplane.py")
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    state = xplane.busy(planes, lo, hi)
    return {"fits": list(fits), "load_module": bench.load_module,
            "trace": {"planes": planes, "lo": lo, "hi": hi,
                      "busy_s": state["busy_s"] if state["chips"] else None,
                      "window_s": (hi - lo) / 1e9}}


# -- a synthetic trace with known answers --------------------------------------

# the chip runs three ops; the four gaps between them (ns):
#   [0, 200]    middle 100: stream:put, inside pass/mean, inside streamed cov
#   [300, 700]  middle 500: stream:sync/count
#   [720, 880]  middle 800: fit:pca only (streamed cov is over, xla eigh not begun)
#   [900, 1000] middle 950: bench_fit only
SYNTHETIC = [
    {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["%gram", 200.0, 100.0], ["%eigh.1", 700.0, 20.0],
        ["%eigh.2", 880.0, 20.0]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ["bench_fit", 0.0, 1000.0], ["fit:pca", 10.0, 930.0],
            ["streamed cov", 20.0, 730.0], ["stream:pass/mean", 30.0, 370.0],
            ["stream:put", 40.0, 160.0], ["stream:put", 210.0, 40.0],
            ["stream:sync/count", 500.0, 200.0], ["xla eigh", 810.0, 90.0],
            # the runtime's own spans are shorter and would win unfiltered
            ["AllocateBufferAwait", 50.0, 100.0],
            ["np.asarray(jax.Array)", 500.0, 190.0]]},
        {"name": "worker", "events": [
            ["Transpose::ExecuteChunk", 790.0, 20.0],
            ["Transpose::ExecuteChunk", 940.0, 20.0]]}]},
]


def test_idle_goes_to_the_innermost_program_span():
    spans = bench.load_module("work/spans.py")
    xplane = bench.load_module("xplane.py")
    ctx = trace_ctx(SYNTHETIC)
    idle = spans.idle_by_program_span(ctx["trace"], xplane)
    assert idle == pytest.approx({
        "stream:put": 200e-9, "stream:sync/count": 400e-9,
        "fit:pca": 160e-9, "bench_fit": 100e-9})
    # unfiltered, the runtime's names take the same gaps
    raw = dict(xplane.idle_gaps(SYNTHETIC, 0.0, 1000.0, n=100))
    assert set(raw) == {"AllocateBufferAwait", "np.asarray(jax.Array)",
                        "Transpose::ExecuteChunk"}
    assert sum(raw.values()) == pytest.approx(sum(idle.values()))
    assert read("idle_in_put_pct", ctx) == pytest.approx(100 * 200 / 860)
    assert read("idle_in_sync_pct", ctx) == pytest.approx(100 * 400 / 860)
    assert read("idle_unattributed_pct", ctx) == pytest.approx(100 * 260 / 860)


def test_the_three_idle_readers_share_one_reduction(monkeypatch):
    xplane = bench.load_module("xplane.py")
    calls = []
    real = xplane.idle_gaps
    monkeypatch.setattr(xplane, "idle_gaps",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    ctx = trace_ctx(SYNTHETIC)
    for name in IDLE_READERS:
        assert read(name, ctx) is not None
    assert len(calls) == 1


def test_span_seconds_sums_and_clips():
    spans = bench.load_module("work/spans.py")
    assert spans.span_seconds(SYNTHETIC, "stream:put") == pytest.approx(200e-9)
    assert spans.span_seconds(SYNTHETIC, "stream:put", 100.0, 220.0) == (
        pytest.approx(110e-9))
    assert spans.span_seconds(SYNTHETIC, "stream:sync/cov") is None
    kept = {e[0] for p in spans.program_planes(SYNTHETIC)
            if p["name"].startswith("/host:")
            for line in p["lines"] for e in line["events"]}
    assert kept <= set(spans.PROGRAM_SPANS) and "stream:put" in kept
    assert spans.program_planes(SYNTHETIC)[0] is SYNTHETIC[0]


def test_a_span_with_no_idle_under_it_reads_zero_not_none():
    planes = [SYNTHETIC[0], {"name": "/host:CPU", "lines": [{
        "name": "main", "events": [
            ["bench_fit", 0.0, 1000.0], ["stream:put", 210.0, 40.0],
            ["stream:sync/cov", 705.0, 5.0]]}]}]
    ctx = trace_ctx(planes)
    assert read("idle_in_put_pct", ctx) == 0.0
    assert read("idle_in_sync_pct", ctx) == 0.0
    assert read("idle_unattributed_pct", ctx) == pytest.approx(100.0)


def test_share_readers_sum_the_windows_fits():
    fits = [{"wall": 1.0, "timings": {"covariance/put": 0.5, "fetch": 0.02,
                                      "covariance/sync": 0.25}},
            {"wall": 3.0, "timings": {"covariance/put": 0.7, "fetch": 0.02,
                                      "covariance/sync": 0.35}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    assert read("put_share_pct", ctx) == pytest.approx(30.0)
    assert read("sync_share_pct", ctx) == pytest.approx(15.0)
    assert read("fetch_share_pct", ctx) == pytest.approx(1.0)
    # a program without the key (the parent): nothing, never a 0 share
    del fits[1]["timings"]["covariance/put"]
    assert read("put_share_pct", ctx) is None
    assert read("sync_share_pct", ctx) == pytest.approx(15.0)
    for name in SHARE_READERS:
        assert read(name, dict(ctx, fits=[])) is None
    # without a device trace the idle readers have nothing to read
    for name in IDLE_READERS:
        assert read(name, ctx) is None
        assert read(name, trace_ctx([SYNTHETIC[1]])) is None


# -- the recorded chip traces ---------------------------------------------------


def test_the_trace_from_before_the_spans_deserves_what_it_reads():
    """``trace_v5e.json.gz`` (PR 25): the program had three spans, so the
    span readers find nothing and the idle time is all but unnamed."""
    planes = bench.load_module("xplane.py").load_recorded(
        os.path.join(HERE, "testdata", "trace_v5e.json.gz"))
    ctx = trace_ctx(planes)
    assert read("idle_in_put_pct", ctx) is None
    assert read("idle_in_sync_pct", ctx) is None
    # 1.332 s idle: 1.265 s under `streamed cov` whole, 0.015 s under
    # `fit:pca`; only the solve's 0.053 s (`xla eigh`) have a name
    assert read("idle_unattributed_pct", ctx) == pytest.approx(96.06, abs=0.01)
    assert sum(ctx["trace"]["idle_by_program_span"].values()) == (
        pytest.approx(ctx["trace"]["window_s"] - ctx["trace"]["busy_s"]))


def test_the_recorded_trace_with_spans_reads_what_was_read_by_hand():
    """``trace_v5e_spans.json.gz``: the first fit of a traced window of
    pca4096-fit-2pass on a v5e (my chip run, PR 26), cut with
    ``record_trace.py``. 1.678 s, the chip busy for 0.224 s of it."""
    xplane = bench.load_module("xplane.py")
    spans = bench.load_module("work/spans.py")
    planes = xplane.load_recorded(
        os.path.join(HERE, "testdata", "trace_v5e_spans.json.gz"))
    ctx = trace_ctx(planes)
    trace = ctx["trace"]
    assert trace["window_s"] == pytest.approx(1.677777804)
    assert trace["busy_s"] == pytest.approx(0.223863366)
    # the program's spans of one two-pass fit of four batches a pass
    seconds = {name: spans.span_seconds(planes, name, trace["lo"], trace["hi"])
               for name in spans.PROGRAM_SPANS}
    assert seconds == pytest.approx({
        "bench_fit": 1.677777804, "fit:pca": 1.677223714,
        "streamed cov": 1.590091326, "stream:pass/mean": 0.111491006,
        "stream:pass/gram": 0.724405843, "stream:pass/stats": None,
        "stream:next": 0.000865651, "stream:put": 0.700820826,
        "stream:accumulate/mean": 0.055649173,
        "stream:accumulate/pallas": 0.009342319,
        "stream:accumulate/xla": None, "stream:sync/count": 0.143079523,
        "stream:sync/cov": 0.427786406, "xla eigh": 0.08369632,
        "fit:fetch": 0.002781359})
    # 1.454 s idle: 0.999 s with the main thread in a put (whole gaps go to
    # the span over their middle, so more than the puts' own 0.701 s),
    # 0.148 s in int(count), 0.254 s in block_until_ready(cov), 0.053 s in
    # the solve, and 0.4 ms that only bench_fit covers
    assert spans.idle_by_program_span(trace, xplane) == pytest.approx({
        "stream:put": 0.998502826, "stream:sync/cov": 0.254235541,
        "stream:sync/count": 0.14820095, "xla eigh": 0.05255729,
        "bench_fit": 0.000417831})
    assert read("idle_in_put_pct", ctx) == pytest.approx(68.677, abs=1e-3)
    assert read("idle_in_sync_pct", ctx) == pytest.approx(27.680, abs=1e-3)
    assert read("idle_unattributed_pct", ctx) == pytest.approx(0.0287,
                                                               abs=1e-4)
    # beside the runtime's names for the same idle seconds
    raw = dict(xplane.idle_gaps(planes, trace["lo"], trace["hi"], n=4))
    assert list(raw) == ["Transpose::ExecuteChunk", "stream:sync/cov",
                         "np.asarray(jax.Array)", "AllocateBufferAwait"]
    # and the accepted readers find on it what they found on the old trace
    gram = bench.load_module("work/gram.py")
    assert xplane.program_seconds(
        planes, gram.PROGRAMS, trace["lo"], trace["hi"]) == pytest.approx({
            "jit_update_mean_stats": 0.012323129,
            "jit__update_centered_gram_fused_blocked": 0.176868946})


# -- a traced run at a tiny size ------------------------------------------------


@pytest.mark.parametrize("cell", ["pca784-fit-2pass", "pca4096-fit-1pass"])
def test_traced_tiny_run_reports_the_share_readers(monkeypatch, cell):
    """Every new metric resolves to its reader through ``run.py`` itself;
    on the CPU (no device plane) the idle readers leave their metric out."""
    spec = tb.tiny_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    result = bench.run(cell, 2 ** 31 + 3, 0.2, True, require_chip=False)
    metrics = result["metrics"]
    for name in SHARE_READERS:
        assert 0.0 <= metrics[name]["value"] <= 100.0, name
    assert metrics["put_share_pct"]["value"] > 0
    assert not set(IDLE_READERS) & set(metrics)
    declared = {m["name"] for m in tb.BENCHMARK["per_layer"]}
    assert set(SHARE_READERS + IDLE_READERS) <= declared
