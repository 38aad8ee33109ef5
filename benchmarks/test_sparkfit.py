"""Tests of what the Spark-front cell adds to the yardstick, on the CPU at a
tiny size.

The cell's files resolve to what ISSUE 36 names; the stand-in frame offers
the ``DataFrame`` surface ``PCA._fit`` calls and nothing else; a whole run
through ``spark.PCA(...).fit(frame)`` is correct under the cell's own
limits, on the device path, with the numbers of the arrow cell for the same
seed to rounding; three planted faults of the driver's merge (a partition's
row left out, a row summed twice, the centring left out) and the
lower-precision control come out as not correct, and a merge in float32 —
which is what the in-process one-pass fit does on the chip — does not and
need not; the four new readers read a synthetic context and read nothing
where a parent has no such span or key. Nothing here is a device number.
"""

from __future__ import annotations

import numpy as np
import pytest
from test_benchmark import bench, run_tiny, tiny_spec

from benchmarks.deploy import spark_stage

CELL = "pca4096-sparkfit-2part"
TWIN = "pca4096-fit-arrow10k"
NEW_READERS = ("handback_share_pct", "merge_share_pct",
               "stage_collect_share_pct", "idle_in_stage_pct")
TINY_RECORD_ROWS = 1000  # 8 x 1000 + 192 of a tiny 8192-row partition


def stage_tiny_spec(cell: str = CELL) -> dict:
    """``tiny_spec`` with the record batches cut in proportion: a tiny
    partition is nine ragged record batches and two device batches."""
    spec = tiny_spec(cell)
    spec["config"]["params"]["recordBatchRows"] = TINY_RECORD_ROWS
    return spec


def run_stage_tiny(monkeypatch, seed: int = 5, cell: str = CELL) -> dict:
    spec = stage_tiny_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    return bench.run(cell, seed, 0.2, False, require_chip=False)


# -- the cell is what ISSUE 36 names ------------------------------------------


def test_the_cell_asks_for_what_the_issue_names():
    spec, twin = bench.load_spec(CELL), bench.load_spec(TWIN)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["config"] == "pca-4096-k256-sparkfit"
    assert spec["cell"]["traffic"] == "fit-sparkfit-2part"
    assert bench.chunk_shape(spec["config"], spec["traffic"]) == (262144, 2)
    assert spec["traffic"]["input_form"] == "iterator"
    assert spec["traffic"]["crossings"] == 1
    assert spec["limits"] == twin["limits"]
    config, base = spec["config"], twin["config"]
    assert config["params"] == {
        "k": 256, "batchRows": 131072, "meanCentering": True,
        "executorDevice": "on", "recordBatchRows": 10000,
        "arrowColumn": "features"}
    for key in ("n_features", "rows_per_fit", "partition_rows", "rows",
                "control", "reference", "runtime_env", "reduced",
                "rows_per_fit_at_source"):
        assert config[key] == base[key], key
    assert config["estimator"] == \
        "benchmarks.deploy.spark_stage:SparkStagePCA"
    assert {"stand_in", "wire_form", "inline_tasks", "arrow_collection",
            "control", "executorDevice"} <= set(config["assumed"])
    for promise in ("whatever the task order", "summed in float64",
                    "an empty partition adds nothing",
                    "a failed task fails the fit"):
        assert promise in config["guarantees"], promise
    # the four readers are this cell's alone
    new = [m for m in bench.read_json(
        bench.os.path.join(bench.ROOT, "BENCHMARK.json"))["per_layer"]
        if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] and m["layer"] == "Spark front"
               and m["moves"] == "fit_rows_per_s" for m in new)
    assert [m["name"] for m in spec["per_layer"]][-4:] == list(NEW_READERS)
    assert not set(NEW_READERS) & {m["name"] for m in twin["per_layer"]}


# -- the stand-in -------------------------------------------------------------


def test_the_frame_offers_what_the_front_calls_and_nothing_more():
    public = {name for name in vars(spark_stage.ColumnarFrame)
              if not name.startswith("_")}
    assert public == {"select", "mapInArrow"}
    mapped = {name for name in vars(spark_stage._MappedStage)
              if not name.startswith("_")}
    assert mapped == {"toArrow", "collect"}
    frame = spark_stage.ColumnarFrame([np.zeros((5, 3), np.float32)], 2, "f")
    assert frame.select("f") is frame
    with pytest.raises(KeyError):
        frame.select("g")


def test_a_task_is_fed_27_batches_and_its_row_crosses_ipc():
    """At the cell's own row counts, at a width that fits the CPU: one task
    a partition, in order, each fed 26 record batches of 10,000 rows and
    one of 2,144; what it yields arrives as other memory with the same
    values, and the frame has timed the crossing."""
    import pyarrow as pa

    spec = bench.load_spec(CELL)
    params = spec["config"]["params"]
    chunks = [np.full((spec["config"]["partition_rows"], 4), i, np.float32)
              for i in range(2)]
    frame = spark_stage.ColumnarFrame(
        iter(chunks), params["recordBatchRows"], params["arrowColumn"])
    seen = []

    def task(batches):
        batches = list(batches)
        seen.append([b.num_rows for b in batches])
        first = batches[0].column(0).flatten().to_numpy()
        assert np.shares_memory(first, chunks[(len(seen) - 1) % 2])  # views
        yield pa.record_batch({"x": pa.array([float(first[0])])})

    table = frame.mapInArrow(task, "x double").toArrow()
    assert seen == [[10000] * 26 + [2144]] * 2
    assert table.column("x").to_pylist() == [0.0, 1.0]
    assert frame.collect_seconds > 0.0
    assert frame.mapInArrow(task, "x double").collect() == [{"x": 0.0},
                                                            {"x": 1.0}]
    # two partitions a fit are four device batches exactly, none masked
    assert 2 * chunks[0].shape[0] == 4 * params["batchRows"]


def test_the_adapter_sets_the_fronts_params_at_once():
    est = spark_stage.SparkStagePCA()
    for name, value in {"k": 4, "batchRows": 64, "recordBatchRows": 10,
                        "arrowColumn": "vec", "gramPrecision": "bfloat16",
                        "executorDevice": "on"}.items():
        assert est.set(name, value) is est
    assert est.own == {"recordBatchRows": 10, "arrowColumn": "vec"}
    assert (est.front.getK(), est.front.getBatchRows()) == (4, 64)
    assert est.front.getGramPrecision() == "bfloat16"
    # a front without the Param (the parent's, for batchRows) refuses here
    with pytest.raises(Exception):
        est.set("noSuchParam", 1)


def test_the_fit_is_the_fronts_own_on_the_one_loop():
    spec = stage_tiny_spec()
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        2 ** 31 + 36, config["n_features"], rows, n_chunks, config["rows"])
    fitted = bench.make_estimator(config).fit(iter(chunks))
    from spark_rapids_ml_tpu.spark.estimator import PCAModel

    assert isinstance(fitted.model, PCAModel)
    assert fitted.pc.shape == (config["n_features"], config["params"]["k"])
    assert fitted.svd_solver_used_ == "randomized"
    extra = fitted.fit_report_.extra
    assert extra["stage"] == {
        "tasks": 2, "stats_rows": 2, "collected_as": "arrow",
        "stats_row_bytes": 8 * (config["n_features"] ** 2
                                + config["n_features"] + 1)}
    assert extra["solve"]["gate"] == "passed"
    ingest = extra["ingest"]
    assert ingest["passes"] == 2 and ingest["batches"] == 4
    assert ingest["chunks"] == 18  # 2 x (8 + 1) record batches
    assert ingest["batches_copied"] == 4 and ingest["batches_viewed"] == 0
    assert ingest["rows_put"] == sum(c.shape[0] for c in chunks)
    for key in ("stage/task", "stage/handback", "stage/merge",
                "stage/collect", "covariance", "covariance/put",
                "covariance/sync", "covariance/next", "covariance/next/read",
                "covariance/next/copy", "covariance/dispatch", "solve",
                "fetch"):
        assert key in fitted.fit_timings_, key


# -- a whole run, sound and broken --------------------------------------------


def test_sound_run_is_correct_under_the_cells_limits(monkeypatch):
    result = run_stage_tiny(monkeypatch, seed=2 ** 31 + 36)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(c["value"] < 0.5 * c["limit"]
               for c in result["compared"].values())


def test_same_seed_reads_the_arrow_cells_numbers_to_rounding(monkeypatch):
    """Same rows, same record batches, same programs a batch; the front
    sums two tasks' moments and centres in float64 where the in-process fit
    centres one stream's in the device's dtype: the compared numbers agree
    to a few parts in a thousand of themselves (equal, where the device
    computes in float64 as this suite may)."""
    stage = run_stage_tiny(monkeypatch, seed=2 ** 31 + 37)
    spec = stage_tiny_spec(TWIN)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    twin = bench.run(TWIN, 2 ** 31 + 37, 0.2, False, require_chip=False)
    assert stage["correct"] and twin["correct"]
    for name in ("ritz_gap", "miss_gap"):
        assert stage["compared"][name]["value"] == pytest.approx(
            twin["compared"][name]["value"], rel=0.05), name
    assert stage["compared"]["mean_gap"]["value"] < 4e-7


def _merge_fault(monkeypatch, combine=None, centre=None):
    from spark_rapids_ml_tpu.spark import estimator as front

    real_combine = front.combine_stats
    real_centre = front.covariance_from_moments
    if combine is not None:
        monkeypatch.setattr(front, "combine_stats",
                            lambda rows: combine(real_combine, list(rows)))
    if centre is not None:
        monkeypatch.setattr(
            front, "covariance_from_moments",
            lambda *args, out=None: centre(real_centre, *args))


def break_a_row_left_out(monkeypatch):
    """The first partition's statistics never reach the sum."""
    _merge_fault(monkeypatch, combine=lambda real, rows: real(rows[1:]))


def break_a_row_summed_twice(monkeypatch):
    """A retried task's row is collected beside the first attempt's."""
    _merge_fault(monkeypatch,
                 combine=lambda real, rows: real([*rows, rows[0]]))


def break_the_centring_left_out(monkeypatch):
    """The covariance is the raw second moment: N mu mu^T never leaves."""
    _merge_fault(monkeypatch, centre=lambda real, gram, col_sum, count, mc:
                 (gram / (count - 1), col_sum / count))


FAULTS = {"a_row_left_out_of_the_merge": break_a_row_left_out,
          "a_row_summed_twice": break_a_row_summed_twice,
          "the_centring_left_out": break_the_centring_left_out}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_merge_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    result = run_stage_tiny(monkeypatch)
    assert not result["correct"], result["compared"]
    assert result["failed"] == 0  # wrong answers, not exceptions


def test_a_float32_merge_reads_like_the_in_process_fit(monkeypatch):
    """ISSUE 36 listed "the merge in float32" among the faults to catch. It
    is not one the limits can see, and they need not: summing and centring
    the moments in float32 is what ``pca4096-fit-1pass`` and
    ``pca4096-fit-arrow10k`` do on the chip (``covariance_from_stats`` in
    the device's dtype), under these same limits. Held here so that nobody
    reads the float64 merge as load-bearing for ``correct``."""
    def through_float32(real, gram, col_sum, count, mc):
        g, s = gram.astype(np.float32), col_sum.astype(np.float32)
        mean = s / np.float32(count)
        cov = (g - np.float32(count) * np.outer(mean, mean)) \
            / np.float32(count - 1)
        return cov.astype(np.float64), mean.astype(np.float64)

    _merge_fault(monkeypatch, centre=through_float32)
    result = run_stage_tiny(monkeypatch, seed=2 ** 31 + 36)
    assert result["correct"], result["compared"]
    assert result["compared"]["ritz_gap"]["value"] < 0.5 * 2e-5


def test_lower_precision_control_is_not_correct():
    """The reference in the program's place, computed in bfloat16, fails the
    cell's limits (the program's own ``gramPrecision=bfloat16`` needs the
    chip to differ: on the CPU XLA ignores the precision; PERF.md), and the
    control's override reaches the front behind the adapter."""
    spec = stage_tiny_spec()
    config = spec["config"]
    assert config["control"]["params"] == {"gramPrecision": "bfloat16"}
    est = bench.make_estimator(config, config["control"]["params"])
    assert est.front.getGramPrecision() == "bfloat16"
    assert est.front._gram_precision() is not None
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


def _trace(stage_spans: bool = True):
    """A 10 s window, the chip busy 0-2 s and 6-7 s. The main thread: a
    task from 0 to 5 s — ``stream:put`` inside it to 3 s, its hand-back
    from 3 to 5 s — then the merge from 5 to 6 s, the solve to 7 s, the
    fetch to 10 s. Idle gaps: 2-6 s (middle 4 s: under the hand-back) and
    7-10 s (middle 8.5 s: under ``fit:fetch``)."""
    stage = [["stage:task", 0.0, 5e9], ["stage:handback", 3e9, 2e9],
             ["stage:merge", 5e9, 1e9]] if stage_spans else []
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        [bench.FIT_SPAN, 0.0, 10e9], ["stream:put", 0.0, 3e9], *stage,
        ["xla eigh", 6e9, 1e9], ["fit:fetch", 7e9, 3e9]]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__update_stats_fused_blocked(1)", 0.0, 2e9],
            ["jit__randomized_solve_program(1)", 6e9, 1e9]]},
        {"name": "XLA Ops", "events": [["%op.0", 0.0, 2e9],
                                       ["%op.1", 6e9, 1e9]]}]}
    planes = [host, device]
    xplane = bench.load_module("xplane.py")
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    return {"planes": planes, "lo": lo, "hi": hi, "window_s": 10.0,
            "busy_s": xplane.busy(planes, lo, hi)["busy_s"]}


def _read(name, ctx):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def test_the_new_readers_on_a_synthetic_context():
    fits = [{"wall": 4.0, "timings": {"stage/handback": 0.4,
                                      "stage/merge": 0.6,
                                      "stage/collect": 0.2}},
            {"wall": 6.0, "timings": {"stage/handback": 0.6,
                                      "stage/merge": 0.9,
                                      "stage/collect": 0.3}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": _trace()}
    assert _read("handback_share_pct", ctx) == pytest.approx(10.0)
    assert _read("merge_share_pct", ctx) == pytest.approx(15.0)
    assert _read("stage_collect_share_pct", ctx) == pytest.approx(5.0)
    # 7 s idle: 4 under the hand-back (innermost of task > hand-back), 3
    # under fit:fetch
    assert _read("idle_in_stage_pct", ctx) == pytest.approx(100 * 4 / 7)
    # the accepted readers do not know the stage's spans: the same 4 s fall
    # to the enclosing coarse span there
    assert _read("idle_unattributed_pct", ctx) == pytest.approx(100 * 4 / 7)
    assert _read("idle_in_put_pct", ctx) == 0.0


def test_the_new_readers_read_nothing_where_there_is_nothing():
    """A parent has no stage: no key, no span. An untraced run has no
    trace. The line leaves the metric out, nothing raises."""
    fits = [{"wall": 2.0, "timings": {"covariance": 1.0, "solve": 0.1}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    for name in NEW_READERS:
        assert _read(name, ctx) is None, name
    ctx["trace"] = _trace(stage_spans=False)  # a program without the spans
    assert _read("idle_in_stage_pct", ctx) is None
    ctx["trace"] = dict(_trace(), busy_s=None)  # a rehearsal on the CPU
    assert _read("idle_in_stage_pct", ctx) is None
    # tasks in other processes: the driver's keys only
    fits[0]["timings"].update({"stage/merge": 0.5, "stage/collect": 0.1})
    assert _read("merge_share_pct", ctx) == pytest.approx(25.0)
    assert _read("handback_share_pct", ctx) is None


def test_a_traced_style_line_reports_every_reader_of_the_cell(monkeypatch):
    """Every per-layer metric the cell lists that reads ``fit_timings_``
    finds its key in a fit of the front (none reads ``null`` for want of
    one): the shared readers' keys are the tasks' summed."""
    spec = stage_tiny_spec()
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        3, config["n_features"], rows, n_chunks, config["rows"])
    fit = bench.fit_once(config, bench.dataset_factory(spec["traffic"],
                                                       chunks))
    ctx = {"fits": [fit], "window_s": fit["wall"], "rows_per_fit": 16384,
           "n_features": config["n_features"], "bytes_put_per_fit": 1 << 26,
           "first_fit_s": fit["wall"], "setup_s": 1.0,
           "compiles_in_window": 0, "trace": None, "peak": None,
           "load_module": bench.load_module}
    from_timings = ("solve_share_pct", "fetch_share_pct", "put_share_pct",
                    "sync_share_pct", "next_share_pct",
                    "chunk_read_share_pct", "reblock_copy_share_pct",
                    "covariance_gbytes_per_s", "handback_share_pct",
                    "merge_share_pct", "stage_collect_share_pct")
    listed = {m["name"] for m in spec["per_layer"]}
    for name in from_timings:
        assert name in listed, name
        assert _read(name, ctx) is not None, name
