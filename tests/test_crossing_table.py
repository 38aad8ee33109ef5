"""``scripts/crossing_table.py`` on the recorded v5e traces (CPU).

The two older traces are one ``pca4096-fit-2pass`` fit each of the loop
before PR 27: eight 2 GiB puts, the first six issued in the fit's first
0.35 s. The table read from them is the finding the put window rests on
(``PERF.md`` §5): the link is FIFO at one batch every 0.151 s, and a landed
batch's step starts only behind the transfers that were queued by then.
They have no landing span of the program's, so ``landed`` falls back to the
runtime's ``Done`` events there. ``trace_v5e_landing.json.gz`` (PR 38, one
fit of ``pca4096-fit-1pass``) has both, and hand-made planes hold the
per-chip reading.
"""

from __future__ import annotations

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = ("trace_v5e_spans.json.gz", "trace_v5e.json.gz")
GIB2_AT_14_19_GBPS = 2 * 2 ** 30 / 14.19e9  # 0.1513 s


@pytest.fixture(scope="module")
def crossing():
    path = os.path.join(ROOT, "scripts", "crossing_table.py")
    spec = importlib.util.spec_from_file_location("crossing_table", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module", params=TRACES)
def table(request, crossing):
    planes = crossing.xplane.load_recorded(
        os.path.join(ROOT, "benchmarks", "testdata", request.param))
    (fit,) = crossing.fit_tables(planes)
    return crossing, fit


def test_eight_batches_dispatched_and_landed_in_order(table):
    _, fit = table
    assert len(fit["retile"]) == len(fit["dispatch"]) == 8
    assert len(fit["landed"]) == 8
    assert fit["dispatch"] == sorted(fit["dispatch"])
    for dispatched, landed in zip(fit["dispatch"], fit["landed"]):
        assert landed > dispatched


def test_the_link_is_fifo_and_full(table):
    _, fit = table
    gaps = [b - a for a, b in zip(fit["landed"], fit["landed"][1:])]
    # batch 2 was dispatched while batch 1 crossed in one trace only; from
    # there on, one batch every 0.1513 s, never two at once
    assert gaps[1:] == pytest.approx([GIB2_AT_14_19_GBPS] * 6, abs=5e-4)
    assert max(gaps) < 0.152


def test_retiling_alone_is_a_third_of_a_crossing(table):
    _, fit = table
    # batches 7 and 8 were re-tiled alone, the first four all at once
    assert fit["retile"][6:] == pytest.approx([0.055] * 2, abs=3e-3)
    assert min(fit["retile"][:4]) > 0.08


def test_steps_start_behind_the_transfers_queued_at_their_landing(table):
    crossing, fit = table
    steps = fit["step"]
    assert [s["program"] for s in steps] == (
        ["jit_update_mean_stats"] * 4
        + ["jit__update_centered_gram_fused_blocked"] * 4)
    # batch 1 landed first and its step ran at landing 4: three transfers
    # were queued behind it; batches 7 and 8 were put late, so one was
    assert [s["behind_landing"] for s in steps] == [4, 5, 6, 6, 7, 8, 8, 8]
    for s in steps[:3]:
        landing = fit["landed"][s["behind_landing"] - 1]
        assert s["start"] == pytest.approx(landing, abs=3e-3)
    summary = crossing.summary([fit])
    assert summary["step_behind_landing"] == [4, 5, 6, 6, 7, 8, 8, 8]
    assert summary["landing_gap_median"] == pytest.approx(0.1513, abs=3e-4)
    # three Gram steps after the last landing
    assert summary["exposed_after_last_landing_median"] == pytest.approx(
        3 * 0.0442, abs=3e-3)


def test_a_trace_without_the_runtimes_names_reads_empty(crossing):
    planes = [{"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["fit:pca", 0.0, 1e9], ["stream:put", 1e6, 2e6]]}]}]
    (fit,) = crossing.fit_tables(planes)
    assert fit["landed"] == fit["dispatch"] == fit["step"] == []
    assert fit["put"] == [0.001]
    assert crossing.summary([fit])["landing_gap_median"] is None


def test_the_older_traces_fall_back_to_the_runtimes_events(table):
    crossing, fit = table
    assert fit["landed_from"] == "runtime"
    assert fit["landed"] == fit["landed_runtime"]
    assert fit["landed_by_chip"] == fit["outstanding_by_chip"] == {}
    summary = crossing.summary([fit])
    assert summary["by_chip"] == {} and summary["landed_from"] == ["runtime"]
    assert summary["program_minus_runtime_landing_max"] is None


def _two_chip_planes() -> list:
    """Two fits of two chips with the program's landing spans and no event
    of the runtime's: chip 0's puts land at 0.2 and 0.5 s of each fit,
    chip 1's at 0.3 and 0.6 s (its second span begins at its first
    landing). A step on chip 0 starts at 0.21 s."""
    s = 1e9
    events = [["fit:pca", 0.0, 1.0 * s], ["fit:pca", 2.0 * s, 1.0 * s]]
    chip0, chip1 = [], []
    for lo in (0.0, 2.0 * s):
        events += [["stream:put", lo + 0.01 * s, 1e6],
                   ["stream:put", lo + 0.02 * s, 1e6]]
        chip0 += [["stream:landing/0", lo + 0.02 * s, 0.18 * s],
                  ["stream:landing/0", lo + 0.25 * s, 0.25 * s]]
        chip1 += [["stream:landing/1", lo + 0.03 * s, 0.27 * s],
                  ["stream:landing/1", lo + 0.30 * s, 0.30 * s]]
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": events},
            {"name": "w0", "events": chip0}, {"name": "w1", "events": chip1}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
            ["jit_update_stats(1)", 0.21 * s, 0.04 * s]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Modules",
                                             "events": []}]}]


def test_landings_are_read_per_chip_from_the_programs_spans(crossing):
    first, second = crossing.fit_tables(_two_chip_planes())
    for fit in (first, second):
        assert fit["landed_from"] == "program"
        assert fit["landed_by_chip"] == {
            0: pytest.approx([0.2, 0.5]), 1: pytest.approx([0.3, 0.6])}
        assert fit["outstanding_by_chip"] == {
            0: pytest.approx([0.02, 0.25]), 1: pytest.approx([0.03, 0.30])}
        # ``landed`` is the first chip's: the chip whose steps are read
        assert fit["landed"] == pytest.approx([0.2, 0.5])
        assert fit["landed_runtime"] == []
    assert [s["behind_landing"] for s in first["step"]] == [1]
    summary = crossing.summary([first, second])
    assert summary["landed_from"] == ["program"]
    assert summary["landing_gap_median"] == pytest.approx(0.3)
    assert sorted(summary["by_chip"]) == [0, 1]
    assert summary["by_chip"][1] == {
        "landings_median": 2,
        "first_landing_median": pytest.approx(0.3),
        "last_landing_median": pytest.approx(0.6),
        "landing_gap_median": pytest.approx(0.3),
        "outstanding_seconds_median": pytest.approx(0.57),
        "span_seconds_median": pytest.approx(0.285)}
    assert summary["by_chip"][0]["outstanding_seconds_median"] \
        == pytest.approx(0.43)
    # no event of the runtime's to hold the program's landings against
    assert summary["program_minus_runtime_landing_max"] is None


@pytest.fixture(scope="module")
def landing_table(crossing):
    planes = crossing.xplane.load_recorded(os.path.join(
        ROOT, "benchmarks", "testdata", "trace_v5e_landing.json.gz"))
    (fit,) = crossing.fit_tables(planes)
    return crossing, fit


def test_the_programs_landings_are_the_runtimes_a_wake_up_later(
        landing_table):
    """One fit of ``pca4096-fit-1pass`` with the put window (my chip run,
    PR 38): four 2 GiB puts; the end of each landing span lies within 5 ms
    behind the runtime's ``Done`` of the same batch — the difference is the
    watcher's wake-up — and the link's pace is read the same from both."""
    crossing, fit = landing_table
    assert fit["landed_from"] == "program"
    assert list(fit["landed_by_chip"]) == [0]
    assert len(fit["landed"]) == len(fit["landed_runtime"]) == 4
    for program, runtime in zip(fit["landed"], fit["landed_runtime"]):
        assert 0.0 <= program - runtime < 5e-3
    for landed in (fit["landed"], fit["landed_runtime"]):
        gaps = [b - a for a, b in zip(landed, landed[1:])]
        assert gaps == pytest.approx([GIB2_AT_14_19_GBPS] * 3, abs=5e-3)
    summary = crossing.summary([fit])
    assert 0.0 <= summary["program_minus_runtime_landing_max"] < 5e-3
    # with two puts in flight step i runs at landing i + 1 (``PERF.md`` §5)
    assert summary["step_behind_landing"] == [2, 3, 4, 4]
    chip = summary["by_chip"][0]
    assert chip["landings_median"] == 4
    assert chip["outstanding_seconds_median"] <= fit["wall"]
