"""Tests of what the Arrow cell adds to the yardstick, on the CPU at a tiny
size.

The cell's files resolve; a whole run through the adapter is correct under
the cell's own limits; the adapter's record batches are views of the host
chunks, 26 + 1 a partition at the cell's own sizes; three planted faults
(a partition's ragged rest left out, a record batch handed over twice, the
reader's columns rolled) and the lower-precision control come out as not
correct; the same seed gives the twin cell's compared numbers; and the
four new readers
read a recorded-style context and read nothing where a parent has no such
key. Nothing here is a device number.
"""

from __future__ import annotations

import numpy as np
import pytest
from test_benchmark import bench, run_tiny, tiny_spec

from benchmarks.deploy import arrow_partition

CELL = "pca4096-fit-arrow10k"
TWIN = "pca4096-fit-1pass"
NEW_READERS = ("next_share_pct", "idle_in_next_pct", "chunk_read_share_pct",
               "reblock_copy_share_pct")
TINY_RECORD_ROWS = 1000  # 8 x 1000 + 192 of a tiny 8192-row partition


def arrow_tiny_spec(cell: str = CELL) -> dict:
    """``tiny_spec`` with the record batches cut in proportion, so that the
    tiny run re-blocks as the cell does: batches of 4096 rows assembled
    across record batches of 1000 and a ragged one of 192."""
    spec = tiny_spec(cell)
    spec["config"]["params"]["recordBatchRows"] = TINY_RECORD_ROWS
    return spec


def run_arrow_tiny(monkeypatch, seed: int = 5) -> dict:
    spec = arrow_tiny_spec()
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    return bench.run(CELL, seed, 0.2, False, require_chip=False)


# -- the cell is what ISSUE 34 names ------------------------------------------


def test_the_cell_asks_for_what_the_issue_names():
    spec, twin = bench.load_spec(CELL), bench.load_spec(TWIN)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["config"] == "pca-4096-k256-arrow10k"
    assert spec["cell"]["traffic"] == "fit-1pass-arrow10k"
    assert bench.chunk_shape(spec["config"], spec["traffic"]) == (262144, 2)
    assert spec["traffic"]["input_form"] == "iterator"
    assert spec["traffic"]["crossings"] == 1
    assert spec["limits"] == twin["limits"]
    config, base = spec["config"], twin["config"]
    own = {name: config["params"][name]
           for name in arrow_partition.OWN_PARAMS}
    assert own == {"recordBatchRows": 10000, "arrowColumn": "features"}
    assert {k: v for k, v in config["params"].items() if k not in own} == \
        base["params"]
    for key in ("n_features", "rows_per_fit", "partition_rows", "rows",
                "control", "reference", "runtime_env", "reduced",
                "rows_per_fit_at_source", "precision"):
        assert config[key] == base[key], key
    assert config["estimator"] == \
        "benchmarks.deploy.arrow_partition:ArrowFedPCA"
    assert {"wire_form", "stand_in", "rows_per_fit"} <= set(config["assumed"])
    # the four readers carry no list of cells: every cell reports them
    new = [m for m in spec["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert all("workloads" not in m for m in new)
    assert [m["name"] for m in twin["per_layer"]][-4:] == list(NEW_READERS)


# -- the adapter --------------------------------------------------------------


def test_a_partition_is_26_batches_of_10000_and_one_of_2144():
    """At the cell's own row counts, at a width that fits the CPU."""
    spec = bench.load_spec(CELL)
    params = spec["config"]["params"]
    chunk = np.zeros((spec["config"]["partition_rows"], 8), dtype=np.float32)
    batches = list(arrow_partition.record_batches(
        [chunk], params["recordBatchRows"], params["arrowColumn"]))
    assert [b.num_rows for b in batches] == [10000] * 26 + [2144]
    assert all(b.schema.names == ["features"] for b in batches)
    assert str(batches[0].schema.field(0).type) == "list<item: float>"
    # two partitions a fit are four device batches exactly, none masked
    assert 2 * chunk.shape[0] == 4 * params["batchRows"]


def test_the_adapters_batches_are_views_of_the_chunk():
    rng = np.random.default_rng(3)
    chunk = rng.normal(size=(2500, 16)).astype(np.float32)
    batches = list(arrow_partition.record_batches([chunk], 1000, "f"))
    assert [b.num_rows for b in batches] == [1000, 1000, 500]
    for i, batch in enumerate(batches):
        values = batch.column(0).flatten().to_numpy(zero_copy_only=True)
        assert np.shares_memory(values, chunk)
        assert values.ctypes.data == chunk[1000 * i:].ctypes.data
        assert np.array_equal(values.reshape(-1, 16),
                              chunk[1000 * i:1000 * (i + 1)])


def test_the_adapter_keeps_its_own_params_and_forwards_the_rest():
    est = arrow_partition.ArrowFedPCA()
    for name, value in {"k": 4, "batchRows": 64, "recordBatchRows": 10,
                        "arrowColumn": "vec", "gramPrecision": "bfloat16"
                        }.items():
        assert est.set(name, value) is est
    assert est.own == {"recordBatchRows": 10, "arrowColumn": "vec"}
    assert (est.pca.getK(), est.pca.getBatchRows()) == (4, 64)
    assert est.pca.get_or_default("gramPrecision") == "bfloat16"
    with pytest.raises(Exception):  # not a Param of PCA either
        est.set("noSuchParam", 1)


@pytest.mark.parametrize("form", ["iterator", "callable"])
def test_the_model_is_pca_fits_own_and_the_fit_re_blocks(form):
    spec = arrow_tiny_spec()
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        2 ** 31 + 34, config["n_features"], rows, n_chunks, config["rows"])
    est = bench.make_estimator(config)
    fitted = est.fit(iter(chunks) if form == "iterator"
                     else (lambda: list(chunks)))
    passes = 1 if form == "iterator" else 2
    assert fitted.svd_solver_used_ == "randomized"
    assert "covariance/next/copy" in fitted.fit_timings_
    ingest = fitted.fit_report_.extra["ingest"]
    assert ingest["passes"] == passes
    assert ingest["chunks"] == 18 * passes  # 2 x (8 + 1) record batches
    assert (ingest["chunk_rows_min"], ingest["chunk_rows_max"]) == (192, 1000)
    assert ingest["chunks_copied"] == 0 and ingest["batches_viewed"] == 0
    assert ingest["batches_copied"] == 4 * passes
    assert ingest["bytes_reblocked"] == passes * sum(c.nbytes for c in chunks)
    # the same rows as whole NumPy chunks: the twin cell's fit, bit for bit
    twin = bench.make_estimator(tiny_spec(TWIN)["config"]).fit(
        iter(chunks) if form == "iterator" else (lambda: list(chunks)))
    assert np.array_equal(fitted.pc, twin.pc)
    assert np.array_equal(fitted.mean, twin.mean)
    assert twin.fit_report_.extra["ingest"]["bytes_reblocked"] == 0


# -- a whole run, sound and broken --------------------------------------------


def test_sound_run_re_blocking_is_correct_under_the_cells_limits(monkeypatch):
    result = run_arrow_tiny(monkeypatch, seed=2 ** 31 + 34)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(c["value"] < 0.5 * c["limit"]
               for c in result["compared"].values())


def test_same_seed_gives_the_twin_cells_compared_numbers(monkeypatch):
    arrow = run_arrow_tiny(monkeypatch, seed=2 ** 31 + 35)
    twin = run_tiny(monkeypatch, TWIN, seed=2 ** 31 + 35)
    assert arrow["compared"] == twin["compared"]


def break_ragged_rest_left_out(monkeypatch):
    """Each partition's last, short record batch never arrives."""
    real = arrow_partition.record_batches

    def record_batches(chunks, batch_rows, column):
        for batch in real(chunks, batch_rows, column):
            if batch.num_rows == batch_rows:
                yield batch

    monkeypatch.setattr(arrow_partition, "record_batches", record_batches)


def break_a_batch_handed_over_twice(monkeypatch):
    """One record batch in three arrives twice."""
    real = arrow_partition.record_batches

    def record_batches(chunks, batch_rows, column):
        for i, batch in enumerate(real(chunks, batch_rows, column)):
            yield batch
            if i % 3 == 1:
                yield batch

    monkeypatch.setattr(arrow_partition, "record_batches", record_batches)


def break_rows_reordered_within_a_batch(monkeypatch):
    """The reader hands back every record batch's columns rolled by one:
    the right numbers in the wrong features."""
    from spark_rapids_ml_tpu.data import arrow

    real = arrow.column_to_matrix
    monkeypatch.setattr(
        arrow, "column_to_matrix",
        lambda chunk, column=None: np.roll(real(chunk, column), 1, axis=1))


FAULTS = {"ragged_rest_left_out": break_ragged_rest_left_out,
          "a_batch_handed_over_twice": break_a_batch_handed_over_twice,
          "columns_rolled_by_the_reader": break_rows_reordered_within_a_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_hand_over_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    result = run_arrow_tiny(monkeypatch)
    assert not result["correct"], result["compared"]
    assert result["failed"] == 0  # wrong answers, not exceptions


def test_lower_precision_control_is_not_correct():
    """The reference in the program's place, computed in bfloat16, fails
    the cell's limits (the program's own ``gramPrecision=bfloat16`` needs
    the chip to differ: on the CPU XLA ignores the precision; PERF.md)."""
    spec = arrow_tiny_spec()
    config = spec["config"]
    assert config["control"]["params"] == {"gramPrecision": "bfloat16"}
    # the control's override reaches the PCA behind the adapter
    est = bench.make_estimator(config, config["control"]["params"])
    assert est.pca.get_or_default("gramPrecision") == "bfloat16"
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        9, config["n_features"], rows, n_chunks, config["rows"])
    ref_module = bench.load_module("reference/pca.py")
    ref = ref_module.reference(chunks)
    control = ref_module.lower_precision_model(chunks, config["params"]["k"])
    correct, compared = ref_module.compare([control], ref, spec["limits"])
    assert not correct
    assert compared["ritz_gap"]["value"] > 3 * spec["limits"]["ritz_gap"]


# -- the four readers ---------------------------------------------------------


def _trace():
    """A 10 s window: the chip busy from 0 to 2 s and from 7 to 8 s; the
    main thread in ``stream:next`` from 2 s to 8 s (a copy span inside it,
    which the idle reduction must not see) and in ``stream:put`` from 8 s
    to 10 s. Two idle gaps: 5 s under ``next``, 2 s under ``put``."""
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        [bench.FIT_SPAN, 0.0, 10e9], ["stream:next", 2e9, 6e9],
        ["stream:next/copy", 2.5e9, 5e9], ["stream:put", 8e9, 2e9]]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__update_stats_fused_blocked(1)", 0.0, 2e9],
            ["jit__update_stats_fused_blocked(1)", 7e9, 1e9]]},
        {"name": "XLA Ops", "events": [["%op.0", 0.0, 2e9],
                                       ["%op.0", 7e9, 1e9]]}]}
    planes = [host, device]
    xplane = bench.load_module("xplane.py")
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    return {"planes": planes, "lo": lo, "hi": hi, "window_s": 10.0,
            "busy_s": xplane.busy(planes, lo, hi)["busy_s"]}


def _read(name, ctx):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def test_the_new_readers_on_a_recorded_style_context():
    fits = [{"wall": 4.0, "timings": {
        "covariance/next": 3.0, "covariance/next/read": 0.02,
        "covariance/next/copy": 2.9}},
        {"wall": 6.0, "timings": {
            "covariance/next": 5.0, "covariance/next/read": 0.03,
            "covariance/next/copy": 4.9}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": _trace()}
    assert _read("next_share_pct", ctx) == pytest.approx(80.0)
    assert _read("chunk_read_share_pct", ctx) == pytest.approx(0.5)
    assert _read("reblock_copy_share_pct", ctx) == pytest.approx(78.0)
    # 7 s idle: 5 under stream:next (its copy span is not a listed span,
    # so those seconds stay with it), 2 under stream:put
    assert _read("idle_in_next_pct", ctx) == pytest.approx(100 * 5 / 7)
    assert _read("idle_in_put_pct", ctx) == pytest.approx(100 * 2 / 7)


def test_the_new_readers_read_nothing_where_there_is_nothing():
    """A parent has ``covariance/next`` but neither finer key; an untraced
    run has no trace: the line leaves the metric out, nothing raises."""
    fits = [{"wall": 2.0, "timings": {"covariance": 1.0,
                                      "covariance/next": 0.04}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    assert _read("next_share_pct", ctx) == pytest.approx(2.0)
    for name in ("chunk_read_share_pct", "reblock_copy_share_pct",
                 "idle_in_next_pct"):
        assert _read(name, ctx) is None
    del fits[0]["timings"]["covariance/next"]
    assert _read("next_share_pct", ctx) is None
    ctx["trace"] = dict(_trace(), busy_s=None)  # a rehearsal on the CPU
    assert _read("idle_in_next_pct", ctx) is None
    # a fit that copied nothing reports the key at zero, and the reader 0
    fits[0]["timings"].update({"covariance/next/read": 0.0,
                               "covariance/next/copy": 0.0})
    assert _read("reblock_copy_share_pct", ctx) == 0.0
    assert _read("chunk_read_share_pct", ctx) == 0.0
