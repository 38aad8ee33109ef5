"""Tests of what the four-chip cell adds to the yardstick, on the CPU at a
tiny size (``conftest.py`` gives the CPU backend four host devices).

Two faults planted in the collectives must come out as not correct under
the cell's own limits; the fit over 1, 2 and 4 chips must pass them, with
the lower-precision control failing; and the three per-layer readers are
checked on a synthetic four-plane trace. Nothing here is a device number.
"""

from __future__ import annotations

import numpy as np
import pytest
from test_benchmark import bench, run_tiny, tiny_spec

CELL = "pca4096-fit-mesh4"


# -- planted faults -----------------------------------------------------------


def break_a_chips_gram_left_out(monkeypatch):
    """The last chip's Gram never reaches collective (b): zeros go in its
    place."""
    import jax.numpy as jnp
    from spark_rapids_ml_tpu.ops import streaming

    real = streaming.collective_sum

    def collective_sum(ingest, parts):
        parts = list(parts)
        parts[-1] = tuple(jnp.zeros_like(a) for a in parts[-1])
        return real(ingest, parts)

    monkeypatch.setattr(streaming, "collective_sum", collective_sum)


def break_local_mean_in_pass_two(monkeypatch):
    """Collective (a) hands every chip the mean of its OWN rows (the count
    stays the global one, so the program's own row guard keeps quiet)."""
    from spark_rapids_ml_tpu.ops import streaming

    real = streaming.collective_mean

    def collective_mean(ingest, mstats):
        _, count = real(ingest, mstats)
        return [s.col_sum / s.count for s in mstats], count

    monkeypatch.setattr(streaming, "collective_mean", collective_mean)


FAULTS = {"a_chips_gram_left_out": break_a_chips_gram_left_out,
          "local_mean_in_pass_two": break_local_mean_in_pass_two}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_collective_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    result = run_tiny(monkeypatch, CELL)
    assert not result["correct"], result["compared"]
    assert result["failed"] == 0  # wrong answers, not exceptions


# -- 1, 2 and 4 chips against the plain reference, and the control ------------


@pytest.fixture(scope="module")
def tiny_rows():
    spec = tiny_spec(CELL)
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        2 ** 31 + 28, config["n_features"], rows, n_chunks, config["rows"])
    ref_module = bench.load_module("reference/pca.py")
    return spec, chunks, ref_module, ref_module.reference(chunks)


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_any_number_of_chips_is_correct_under_the_cells_limits(tiny_rows,
                                                                chips):
    spec, chunks, ref_module, ref = tiny_rows
    fit = bench.fit_once(spec["config"], lambda: (lambda: list(chunks)),
                         {"numDevices": chips})
    correct, compared = ref_module.compare([fit["model"]], ref,
                                           spec["limits"])
    assert correct, compared
    # the readings sit well inside the limits, not at their edge
    assert all(c["value"] < 0.5 * c["limit"] for c in compared.values())


def test_lower_precision_control_is_not_correct(tiny_rows):
    spec, chunks, ref_module, ref = tiny_rows
    control = ref_module.lower_precision_model(chunks,
                                               spec["config"]["params"]["k"])
    correct, compared = ref_module.compare([control], ref, spec["limits"])
    assert not correct
    assert compared["ritz_gap"]["value"] > 3 * spec["limits"]["ritz_gap"]


def test_the_cell_asks_for_what_the_issue_names():
    spec = bench.load_spec(CELL)
    assert spec["cell"]["chips"] == spec["config"]["params"]["numDevices"] == 4
    rows, n_chunks = bench.chunk_shape(spec["config"], spec["traffic"])
    assert (rows, n_chunks) == (262144, 8)
    assert spec["traffic"]["crossings"] == 1
    assert spec["limits"] == bench.load_spec("pca4096-fit-2pass")["limits"]
    # the one-chip configuration's shapes, but for the chips and the rows
    one = bench.load_spec("pca4096-fit-2pass")["config"]
    four = spec["config"]
    assert {k: v for k, v in four["params"].items() if k != "numDevices"} == \
        one["params"]
    for key in ("n_features", "partition_rows", "rows", "control",
                "reference", "estimator", "rows_per_fit_at_source"):
        assert four[key] == one[key], key
    assert four["rows_per_fit"] == 4 * one["rows_per_fit"]
    new = [m["name"] for m in spec["per_layer"] if "workloads" in m]
    assert {"collective_share_pct", "collective_device_ms_per_fit",
            "chip_busy_skew_pct"} <= set(new)


# -- the three readers on a synthetic four-plane trace ------------------------


def _trace(busy_per_chip, collective_per_chip):
    """A window of 10 s; chip i runs one accumulate program of
    ``busy_per_chip[i]`` seconds and one all-reduce of each kind."""
    planes = [{"name": "/host:CPU", "lines": [{"name": "main", "events": [
        [bench.FIT_SPAN, 0.0, 10e9]]}]}]
    for i, (busy, coll) in enumerate(zip(busy_per_chip, collective_per_chip)):
        modules = [["jit__update_centered_gram_fused_blocked(1)", 1e9,
                    busy * 1e9],
                   ["jit_all_reduce_mean(2)", 6e9, coll * 1e9],
                   ["jit_all_reduce_sum(3)", 7e9, coll * 1e9]]
        planes.append({"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": [[f"%op.{j}", s, d]
                                           for j, (_, s, d) in
                                           enumerate(modules)]}]})
    xplane = bench.load_module("xplane.py")
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    return {"planes": planes, "lo": lo, "hi": hi, "window_s": 10.0,
            "busy_s": xplane.busy(planes, lo, hi)["busy_s"]}


def _read(name, ctx):
    return bench.load_module(f"metrics/{name}.py").read(ctx)


def test_collective_readers_on_four_planes():
    fits = [{"wall": 2.0, "timings": {"covariance/collective": 0.004}},
            {"wall": 2.0, "timings": {"covariance/collective": 0.006}}]
    ctx = {"fits": fits, "load_module": bench.load_module,
           "trace": _trace([2.0, 1.5, 1.0, 1.0], [0.010] * 4)}
    assert _read("collective_share_pct", ctx) == pytest.approx(0.25)
    # 4 chips x 2 programs x 10 ms over 2 fits
    assert _read("collective_device_ms_per_fit", ctx) == pytest.approx(40.0)
    # busiest 2.02 s, least busy 1.02 s
    assert _read("chip_busy_skew_pct", ctx) == pytest.approx(
        100 * 1.0 / 2.02)
    # the accepted readers take four planes: idle averaged over the chips
    assert _read("device_idle_pct", ctx) == pytest.approx(
        100 * (1 - (5.5 + 0.08) / 4 / 10.0))


def test_collective_readers_read_nothing_where_there_is_nothing():
    """A parent without the spans and programs, an untraced run, one chip:
    the line leaves the metric out, nothing raises."""
    fits = [{"wall": 2.0, "timings": {"covariance": 1.0}}]
    ctx = {"fits": fits, "load_module": bench.load_module, "trace": None}
    for name in ("collective_share_pct", "collective_device_ms_per_fit",
                 "chip_busy_skew_pct"):
        assert _read(name, ctx) is None
    one_chip = _trace([2.0], [0.0])
    one_chip["planes"][1]["lines"][0]["events"] = one_chip["planes"][1][
        "lines"][0]["events"][:1]  # no collective program
    ctx["trace"] = one_chip
    assert _read("collective_device_ms_per_fit", ctx) is None
    assert _read("chip_busy_skew_pct", ctx) is None
    cpu = dict(one_chip, busy_s=None)  # a rehearsal without device planes
    ctx["trace"] = cpu
    assert _read("collective_device_ms_per_fit", ctx) is None
    assert _read("chip_busy_skew_pct", ctx) is None
