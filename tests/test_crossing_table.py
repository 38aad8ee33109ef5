"""``scripts/crossing_table.py`` on the two recorded v5e traces (CPU).

The traces are one ``pca4096-fit-2pass`` fit each of the loop before PR 27:
eight 2 GiB puts, the first six issued in the fit's first 0.35 s. The table
read from them is the finding the put window rests on (``PERF.md`` §5): the
link is FIFO at one batch every 0.151 s, and a landed batch's step starts
only behind the transfers that were queued by then.
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
