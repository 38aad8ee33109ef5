"""A second estimator family is a cell with new files only: the harness,
on the CPU, through its own doors, on two fixture configurations that
``BENCHMARK.json`` does not list.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_families.py -q -p no:cacheprovider

The fixtures live under ``testdata/families/`` in the benchmark's own
layout (``configs/``, ``traffic/``, ``cells/``, ``reference/``, ``work/``,
and ``fixtures.json`` in the form of ``BENCHMARK.json``), so a
``model_config`` PR copies them into real configurations file for file:

- LinearRegression over ``linear``-labelled rows, fitted from a callable of
  ``(X, y)`` chunks (the streamed Z = [X | y] path); its reference solves
  the normal equations from a float32 Gram at ``highest`` precision, in
  float64 on the host;
- KMeans over clustered rows, fitted from a callable; its reference runs
  one plain Lloyd step from the program's own centres.

For each: a sound run is ``correct``, each planted fault of its own
``FAULTS`` is not, and ``fit_mfu`` reads its own work module, not the
Gram's. Nothing here is a device number.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb  # noqa: E402

bench = tb.bench
HOME = os.path.join("testdata", "families")
FIXTURES = bench.read_json(os.path.join(HERE, HOME, "fixtures.json"))
CELLS = [w["name"] for w in FIXTURES["workloads"]]
REAL_LOAD_SPEC = bench.load_spec
# the per-layer metrics every cell reports (no ``workloads`` key)
HOST_READERS = {"fit_wall_max_s", "fit_mfu", "first_fit_s",
                "compiles_in_window"}
DEVICE_READERS = {"accumulate_roofline", "device_idle_pct",
                  "idle_unattributed_pct"}


def fixture_spec(cell: str) -> dict:
    """The fixture's files, cut for the CPU by its own ``tiny`` group."""
    return tb.cut_for_cpu(REAL_LOAD_SPEC(cell, home=HOME))


def faults_of(cell: str) -> dict:
    return bench.reference_of(fixture_spec(cell)["config"], HOME).FAULTS


def run_fixture(monkeypatch, cell: str, seed: int, trace: bool = False,
                **kwargs) -> dict:
    spec = fixture_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    return bench.run(cell, seed, 0.2, trace, **kwargs)


def test_the_fixtures_are_not_cells_of_the_benchmark():
    assert not set(CELLS) & set(tb.CELLS)
    names = {c["name"] for c in tb.BENCHMARK["configs"]}
    assert not {c["name"] for c in FIXTURES["configs"]} & names


@pytest.mark.parametrize("cell", CELLS)
def test_a_fixture_resolves_to_its_own_files(cell):
    spec = fixture_spec(cell)
    config = spec["config"]
    assert spec["home"] == HOME
    reference = bench.reference_of(config, HOME)
    assert callable(reference.model_of) and reference.FAULTS
    assert os.path.dirname(reference.__file__) == os.path.join(
        HERE, HOME, "reference")
    work = bench.work_of(config, HOME)
    assert work.PROGRAMS and callable(work.flops)
    assert set(spec["limits"]) and {m["name"] for m in spec["per_layer"]} \
        >= {"fit_mfu"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_fixture_run_is_correct(monkeypatch, cell):
    result = run_fixture(monkeypatch, cell, 2 ** 31 + 41,
                         require_chip=False)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["compared"]) == set(fixture_spec(cell)["limits"])
    assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS for fault in sorted(faults_of(cell))],
    ids=[f"{cell}-{fault}" for cell in CELLS
         for fault in sorted(faults_of(cell))])
def test_a_planted_fault_of_the_fixtures_own_is_not_correct(
        monkeypatch, cell, fault):
    faults_of(cell)[fault](monkeypatch)
    result = run_fixture(monkeypatch, cell, 5, require_chip=False)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_fit_mfu_reads_the_fixtures_own_work(monkeypatch, cell):
    """A traced run with the chip's door stood in for (the CPU's device,
    the v5e's peaks): ``fit_mfu`` is the fixture's work over the window,
    and the Gram's work is never asked."""
    import jax

    calls = {"own": [], "gram": []}
    gram = bench.load_module("work/gram.py")
    monkeypatch.setattr(gram, "flops", lambda *a: calls["gram"].append(a))
    real_work_of = bench.work_of

    def recording_work_of(config, home=""):
        work = real_work_of(config, home)
        real = work.flops

        def flops(rows, n):
            calls["own"].append((rows, n))
            return real(rows, n)

        monkeypatch.setattr(work, "flops", flops)
        return work

    monkeypatch.setattr(bench, "work_of", recording_work_of)
    monkeypatch.setattr(bench, "find_chips",
                        lambda chips: (jax.devices()[:1], tb.V5E))
    monkeypatch.setattr(bench, "configure_cache", lambda: None)
    # the fixture listed as a cell of the benchmark would be: every
    # per-layer metric of BENCHMARK.json that names no cells is its too
    spec = fixture_spec(cell)
    spec["per_layer"] = [m for m in tb.BENCHMARK["per_layer"]
                         if "workloads" not in m]
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    result = bench.run(cell, 2 ** 31 + 42, 0.2, True)
    config = spec["config"]
    assert result["correct"], result["compared"]
    assert "fit_mfu" in result["metrics"] and not calls["gram"]
    assert calls["own"] == [(config["rows_per_fit"] * result["fits"],
                             config["n_features"])]
    assert 0.0 < result["metrics"]["fit_mfu"]["value"] < 100.0
    # the host's readers read; those of the device trace need a device
    # plane, which the CPU's trace has not (on the chip: the work's
    # PROGRAMS for the roofline, any trace for the two idle shares)
    listed = {m["name"] for m in spec["per_layer"]}
    assert listed == HOST_READERS | DEVICE_READERS
    assert set(result["metrics"]) == HOST_READERS


def test_the_two_works_differ_from_the_grams():
    gram = bench.load_module("work/gram.py")
    rows, n = 1 << 20, 256
    works = {cell: bench.work_of(fixture_spec(cell)["config"], HOME)
             for cell in CELLS}
    linreg, kmeans = works["linreg-xy-2chunk"], works["kmeans-clusters-2chunk"]
    assert linreg.flops(rows, n) == gram.flops(rows, n + 1)
    assert kmeans.flops(rows, n) == 4 * rows * 8 * n
    assert kmeans.least_seconds(rows, n, tb.V5E)[1] == "memory"


# -- the rows' optional groups ------------------------------------------------


BASE = {"spectrum_power": 0.5, "mean_scale": 0.1, "row_scale_sigma": 1.0}


def test_labelled_rows_are_x_beta_plus_noise_and_x_is_the_plain_rows():
    rows = bench.load_module("rows.py")
    seed = 2 ** 31 + 43
    plain = rows.make_chunks(seed, 16, 256, 2, BASE)
    linear = rows.make_chunks(seed, 16, 256, 2, dict(
        BASE, labels={"kind": "linear", "noise": 0.0}))
    binary = rows.make_chunks(seed, 16, 256, 2, dict(
        BASE, labels={"kind": "binary", "noise": 0.1}))
    assert all(isinstance(c, tuple) and c[0].shape == (256, 16)
               and c[1].shape == (256,) and c[1].dtype == np.float32
               for c in linear)
    # the labels take nothing from the rows' own draws
    assert all(np.array_equal(x, lx) for x, (lx, _) in zip(plain, linear))
    x = np.concatenate([c[0] for c in linear]).astype(np.float64)
    y = np.concatenate([c[1] for c in linear]).astype(np.float64)
    beta, *_ = np.linalg.lstsq(x, y, rcond=None)
    assert np.max(np.abs(x @ beta - y)) < 1e-4 * np.max(np.abs(y))
    assert set(np.unique(np.concatenate([c[1] for c in binary]))) == {0, 1}


def test_clustered_rows_sit_around_skewed_centres():
    rows = bench.load_module("rows.py")
    seed = 2 ** 31 + 44
    plain = np.concatenate(rows.make_chunks(seed, 8, 4096, 2, BASE))
    spread = 0.01
    clustered = np.concatenate(rows.make_chunks(seed, 8, 4096, 2, dict(
        BASE, clusters={"k": 4, "spread": spread, "skew": 1.5})))
    centres = np.round(clustered - spread * plain, 4)
    found, counts = np.unique(centres, axis=0, return_counts=True)
    assert len(found) == 4
    counts = np.sort(counts)[::-1]
    assert counts[0] > 2 * counts[-1]   # Zipf(1.5): the first is the largest
    uniform = np.concatenate(rows.make_chunks(seed, 8, 4096, 2, dict(
        BASE, clusters={"k": 4, "spread": spread, "skew": 0.0})))
    _, even = np.unique(np.round(uniform - spread * plain, 4), axis=0,
                        return_counts=True)
    assert even.min() > 0.8 * even.max()
