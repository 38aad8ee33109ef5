"""Tests of the readers of the host batch's slices (``metrics/puts_per_batch.py``
and ``metrics/landing_tail_ms.py``), on the CPU: hand-made reports with
known answers, reports of a program without the counters (which read
nothing), and a tiny fit of every cell with the program's put limit held
down to half a batch, so that each host batch crosses in two slices.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_slices.py -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb  # noqa: E402 - the cells cut to the CPU's size
from test_crossing import _chip, _ctx, _door, _report  # noqa: E402
from test_sparkfit import stage_tiny_spec  # noqa: E402

from spark_rapids_ml_tpu.ops import streaming  # noqa: E402

bench = tb.bench
READERS = ("puts_per_batch", "landing_tail_ms")


def _read(name, ctx):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def _sliced_report(fit: int, tail: float):
    # four landings a chip, two chips: eight puts of four host batches
    return _report(fit, [_chip("TPU_0", 0.8, 1.0), _chip("TPU_1", 1.0, 1.2)],
                   bytes_put=16, put_wait_seconds=0.1, host_batches=4,
                   landing_tail_seconds=tail)


def test_the_readers_on_hand_made_reports(monkeypatch):
    _door(monkeypatch, [_sliced_report(0, 0.050), _sliced_report(1, 0.060)])
    ctx = _ctx()
    assert _read("puts_per_batch", ctx) == pytest.approx(2.0)
    assert _read("landing_tail_ms", ctx) == pytest.approx(55.0)


def test_the_readers_read_nothing_without_the_counters(monkeypatch):
    """The parent's reports: ``batches`` and the landings, no
    ``host_batches`` and no ``landing_tail_seconds``."""
    _door(monkeypatch, [_report(f, [_chip("TPU_0", 0.8, 1.0)],
                                bytes_put=8, put_wait_seconds=0.1)
                        for f in range(2)])
    for name in READERS:
        assert _read(name, _ctx()) is None, name
    # one fit of two lacks them: nothing is read from the window
    _door(monkeypatch, [_sliced_report(0, 0.05), _report(
        1, [_chip("TPU_0", 0.8, 1.0), _chip("TPU_1", 1.0, 1.2)],
        bytes_put=16, put_wait_seconds=0.1)])
    for name in READERS:
        assert _read(name, _ctx()) is None, name
    # no door at all
    from spark_rapids_ml_tpu.obs import report as report_module

    monkeypatch.delattr(report_module, "recent_fit_reports")
    for name in READERS:
        assert _read(name, _ctx()) is None, name


def test_the_metrics_are_listed_with_their_readers():
    listed = {m["name"]: m for m in tb.BENCHMARK["per_layer"]}
    layer = listed["bytes_put_per_fit_gb"]["layer"]
    for name in READERS:
        assert callable(bench.load_module(f"metrics/{name}.py").read)
        assert listed[name]["layer"] == layer
        assert listed[name]["moves"] == "fit_rows_per_s"
    assert set(listed["puts_per_batch"]["workloads"]) == set(tb.CELLS)
    assert "kmeans3000-lloyd-2part" not in listed["landing_tail_ms"][
        "workloads"]
    # appended: every metric the benchmark had keeps its place
    assert [m["name"] for m in tb.BENCHMARK["per_layer"][-2:]] == list(
        READERS)


@pytest.mark.parametrize("cell", tb.CELLS)
def test_a_tiny_fit_with_two_slices_a_batch_reads_both(monkeypatch, cell):
    spec = stage_tiny_spec(cell) if "recordBatchRows" in tb.tiny_spec(
        cell)["config"]["params"] else tb.tiny_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    config = spec["config"]
    # float32 host rows: half a host batch's bytes
    monkeypatch.setattr(streaming, "PUT_BYTES_MAX", config["params"][
        "batchRows"] * config["n_features"] * 4 // 2)
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        3, config["n_features"], rows, n_chunks, config["rows"])
    new_dataset = bench.dataset_factory(spec["traffic"], chunks)
    fits = [bench.fit_once(config, new_dataset) for _ in range(2)]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    listed = {m["name"] for m in spec["per_layer"]}
    assert "puts_per_batch" in listed
    assert _read("puts_per_batch", ctx) == pytest.approx(2.0)
    tail = _read("landing_tail_ms", ctx)
    if "landing_tail_ms" in listed:
        assert isinstance(tail, float)
