"""Tests of the benchmark's own yardstick, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider

They check the trace reduction on a recorded chip trace, the work counts
on known shapes, that every cell of ``BENCHMARK.json`` resolves to its
files, that the lower-precision control comes out as not correct, and
that a run whose timed path is broken underneath reports ``correct``
false. Nothing here is a device number.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

_spec = importlib.util.spec_from_file_location(
    "bench_run", os.path.join(HERE, "run.py"))
bench = importlib.util.module_from_spec(_spec)
sys.modules["bench_run"] = bench
_spec.loader.exec_module(bench)

BENCHMARK = bench.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
V5E = bench.read_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# -- the trace reduction, on a recorded chip trace ---------------------------


@pytest.fixture(scope="module")
def recorded():
    xplane = bench.load_module("xplane.py")
    return xplane, xplane.load_recorded(
        os.path.join(HERE, "testdata", "trace_v5e.json.gz"))


def test_union_merges_overlaps_and_clips():
    xplane = bench.load_module("xplane.py")
    events = [["a", 0.0, 10.0], ["b", 5.0, 10.0], ["c", 30.0, 5.0]]
    seconds, merged = xplane.union_seconds(events)
    assert merged == [[0.0, 15.0], [30.0, 35.0]]
    assert seconds == pytest.approx(20e-9)
    seconds, merged = xplane.union_seconds(events, lo=10.0, hi=32.0)
    assert merged == [[10.0, 15.0], [30.0, 32.0]]


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    """One fit of pca4096-fit-2pass on a v5e (my chip run, PR 25)."""
    xplane, planes = recorded
    assert [p["name"] for p in xplane.device_planes(planes)] == [
        "/device:TPU:0"]
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    assert (hi - lo) / 1e9 == pytest.approx(1.556269914)
    state = xplane.busy(planes, lo, hi)
    assert state["chips"] == 1
    assert state["busy_s"] == pytest.approx(0.223888004)
    gram = bench.load_module("work/gram.py")
    programs = xplane.program_seconds(planes, gram.PROGRAMS, lo, hi)
    assert programs == pytest.approx({
        "jit_update_mean_stats": 0.012322341,
        "jit__update_centered_gram_fused_blocked": 0.176868096})
    assert xplane.top_device_ops(planes, lo, hi, 1)[0] == [
        "%_fused_centered_gram.1", pytest.approx(0.172239297)]
    gaps = xplane.idle_gaps(planes, lo, hi, n=1000)
    assert gaps[0][0] == "Transpose::ExecuteChunk"
    # every idle second goes to some host span, none twice
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo) / 1e9 - state["busy_s"])


def test_readers_on_the_recorded_trace(recorded):
    xplane, planes = recorded
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    busy_s = xplane.busy(planes, lo, hi)["busy_s"]
    ctx = {"fits": [{"wall": 1.5563, "timings": {"covariance": 1.4,
                                                  "solve": 0.1}}],
           "window_s": 1.5563, "rows_per_fit": 524288, "n_features": 4096,
           "bytes_put_per_fit": 2 * 524288 * 4096 * 4, "first_fit_s": 5.9,
           "compiles_in_window": 0, "peak": V5E,
           "load_module": bench.load_module,
           "trace": {"planes": planes, "lo": lo, "hi": hi, "busy_s": busy_s,
                     "window_s": (hi - lo) / 1e9}}

    def read(name):
        return bench.load_module(f"metrics/{name}.py").read(ctx)

    # least time 44.7 ms (compute-bound) over 189.2 ms of accumulate programs
    assert read("accumulate_roofline") == pytest.approx(23.6, abs=0.1)
    assert read("device_idle_pct") == pytest.approx(85.6, abs=0.1)
    assert read("fit_mfu") == pytest.approx(2.87, abs=0.02)
    assert read("solve_share_pct") == pytest.approx(100 * 0.1 / 1.5563)
    assert read("covariance_gbytes_per_s") == pytest.approx(
        2 * 524288 * 4096 * 4 / 1.4 / 1e9)
    assert read("compiles_in_window") == 0
    # nothing to read -> nothing reported, never a 0 share
    ctx["trace"] = None
    assert read("accumulate_roofline") is None
    assert read("device_idle_pct") is None


def test_xplane_adapter_reads_a_fresh_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    xplane = bench.load_module("xplane.py")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(bench.FIT_SPAN):
        jnp.ones((256, 256)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = xplane.load(xplane.find_xplane(str(tmp_path)))
    lo, hi = xplane.window(planes, bench.FIT_SPAN)
    assert hi > lo
    assert xplane.device_planes(planes) == []  # the CPU has no such plane
    assert xplane.busy(planes, lo, hi)["chips"] == 0


# -- work counts --------------------------------------------------------------


def test_gram_work_on_known_shapes():
    gram = bench.load_module("work/gram.py")
    assert gram.flops(8192, 4096) == 8192 * 4096 * 4097
    assert gram.bytes_read(8192, 4096) == 128 << 20
    # the 0.70 ms peak-FLOPs floor of one 8192x4096 step (PERF.md, PR 21)
    seconds, bound = gram.least_seconds(8192, 4096, V5E)
    assert bound == "compute" and seconds == pytest.approx(0.698e-3, rel=1e-2)
    seconds, bound = gram.least_seconds(2097152, 784, V5E)
    assert bound == "memory" and seconds == pytest.approx(8.03e-3, rel=1e-2)


# -- BENCHMARK.json resolves, by name, to files -------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    spec = bench.load_spec(cell)
    config, traffic = spec["config"], spec["traffic"]
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == spec["cell"]["config"])
    assert config["name"] == entry["name"]
    assert set(entry["reduced"]) == set(config["reduced"])
    assert all(key in config for key in config["reduced"])
    rows, chunks = bench.chunk_shape(config, traffic)
    assert rows * chunks == config["rows_per_fit"]
    assert rows % config["params"]["batchRows"] == 0  # no host re-blocking
    assert os.path.exists(os.path.join(
        HERE, "reference", config["reference"] + ".py"))
    assert set(spec["limits"]) == {"mean_gap", "ritz_gap", "miss_gap"}
    for metric in spec["per_layer"]:
        reader = bench.load_module(f"metrics/{metric['name']}.py")
        assert callable(reader.read)
    assert {m["name"] for m in spec["end_to_end"]} == {"fit_rows_per_s",
                                                       "setup_s"}


def test_benchmark_json_keeps_to_the_contracts_shapes():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    names += CELLS + [c["name"] for c in BENCHMARK["configs"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert all(len(w["why"]) <= 200 for w in BENCHMARK["workloads"])
    assert all(len(c["source"]) <= 200 and len(c["why"]) <= 200
               for c in BENCHMARK["configs"])
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCHMARK["per_layer"])
    assert all(0 < m["bound"] <= 0.1 for m in BENCHMARK["end_to_end"])


# -- rows and reference -------------------------------------------------------

RECIPE = {"spectrum_power": 0.5, "mean_scale": 0.1, "row_scale_sigma": 1.0}


def test_same_seed_same_rows_and_large_seeds_differ():
    rows = bench.load_module("rows.py")
    big = 2 ** 31 + 7
    a = rows.make_chunks(big, 32, 64, 2, RECIPE)
    b = rows.make_chunks(big, 32, 64, 2, RECIPE)
    c = rows.make_chunks(7, 32, 64, 2, RECIPE)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and a[0].flags.c_contiguous
    assert not np.array_equal(a[0], a[1])


def test_reference_agrees_with_numpy_float64():
    rows = bench.load_module("rows.py")
    ref_module = bench.load_module("reference/pca.py")
    chunks = rows.make_chunks(3, 48, 4096, 2, RECIPE)
    ref = ref_module.reference(chunks)
    x = np.concatenate(chunks).astype(np.float64)
    mean = x.mean(axis=0)
    cov = (x - mean).T @ (x - mean) / (x.shape[0] - 1)
    assert np.max(np.abs(ref["mean"] - mean)) < 1e-7
    assert np.max(np.abs(ref["cov"] - cov)) / np.max(np.abs(cov)) < 1e-6
    # the exact model reads (nearly) nothing on every number
    evals, evecs = np.linalg.eigh(cov)
    exact = {"pc": evecs[:, ::-1][:, :8], "mean": mean,
             "explained_variance": evals[::-1][:8] / evals.sum()}
    gaps = ref_module.gaps(exact, ref)
    assert max(abs(v) for v in gaps.values()) < 1e-5
    # and a NaN or a missing limit is never correct
    limits = {"mean_gap": 1.0, "ritz_gap": 1.0, "miss_gap": 1.0}
    assert ref_module.compare([exact], ref, limits)[0]
    broken = dict(exact, pc=exact["pc"] * np.nan)
    assert not ref_module.compare([exact, broken], ref, limits)[0]
    assert not ref_module.compare([broken, exact], ref, limits)[0]
    assert not ref_module.compare([exact], ref, {"mean_gap": 1.0})[0]
    assert not ref_module.compare([], ref, limits)[0]


# -- a whole run at a tiny size, sound and broken -----------------------------


def tiny_spec(cell: str) -> dict:
    """The cell's own files with its sizes cut for the CPU: same estimator,
    Params, traffic form, reference and LIMITS."""
    spec = copy.deepcopy(bench.load_spec(cell))
    config = spec["config"]
    if config["n_features"] >= 1024:   # keeps svdSolver=auto on randomized
        config["n_features"], config["params"]["k"] = 1024, 64
    else:
        config["n_features"], config["params"]["k"] = 96, 8
    config["partition_rows"], config["params"]["batchRows"] = 8192, 4096
    config["rows_per_fit"] = 16384
    return spec


def run_tiny(monkeypatch, cell: str, seed: int = 5) -> dict:
    spec = tiny_spec(cell)
    monkeypatch.setattr(bench, "load_spec", lambda *a, **k: spec)
    result = bench.run(cell, seed, 0.2, False, require_chip=False)
    json.dumps(result)  # the line has to serialise
    assert list(result)[-1] == "compared"
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_under_the_cells_own_limits(monkeypatch, cell):
    result = run_tiny(monkeypatch, cell, seed=2 ** 31 + 11)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}


def _skip_second_call(real):
    calls = {"n": 0}

    def step(acc, *args, **kwargs):
        calls["n"] += 1
        return acc if calls["n"] % 4 == 2 else real(acc, *args, **kwargs)

    return step


def _half_batch(real):
    def step(acc, batch, *args, **kwargs):
        import jax.numpy as jnp

        half = batch[: batch.shape[0] // 2]
        return real(acc, jnp.concatenate([half, half]), *args, **kwargs)

    return step


def break_state_unchanged(monkeypatch):
    """One accumulate step in four hands its state back unchanged."""
    from spark_rapids_ml_tpu.ops import streaming

    for name in ("update_centered_gram_auto", "update_stats_auto"):
        monkeypatch.setattr(streaming, name,
                            _skip_second_call(getattr(streaming, name)))


def break_half_batch(monkeypatch):
    """Every accumulate step (mean pass too) leaves half of its batch out
    and takes the mean and the covariance over the rest. The rest stands
    in twice, so that the row count stays what the program's own guard
    (pass 1 against pass 2) expects."""
    from spark_rapids_ml_tpu.ops import streaming

    for name in ("update_mean_stats", "update_centered_gram_auto",
                 "update_stats_auto"):
        monkeypatch.setattr(streaming, name,
                            _half_batch(getattr(streaming, name)))


def _alter(field):
    def breaker(monkeypatch):
        from spark_rapids_ml_tpu.models import pca

        real = pca.PCAModel.__init__

        def init(self, *args, **kwargs):
            if field == "explained_variance":
                kwargs[field] = np.array(kwargs[field]) * 1.001
            else:
                kwargs[field] = np.array(kwargs[field])
                kwargs[field][..., 0] += 1e-3
            real(self, *args, **kwargs)

        monkeypatch.setattr(pca.PCAModel, "__init__", init)

    breaker.__doc__ = f"The answer's {field} altered where it is produced."
    return breaker


FAULTS = {
    "state_unchanged": break_state_unchanged,
    "half_batch": break_half_batch,
    "pc_altered": _alter("pc"),
    "variance_altered": _alter("explained_variance"),
    "mean_altered": _alter("mean"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    result = run_tiny(monkeypatch, cell)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("cell", ["pca4096-fit-2pass", "pca784-fit-2pass"])
def test_lower_precision_control_is_not_correct(cell):
    """The reference put in the program's place and computed in bfloat16
    fails the cell's limits; in float32 it passes them. (The program's own
    bfloat16 Gram needs the chip to differ from float32: on the CPU XLA
    ignores the precision. Its readings are in PERF.md.)"""
    spec = tiny_spec(cell)
    config = spec["config"]
    rows, n_chunks = bench.chunk_shape(config, spec["traffic"])
    chunks = bench.load_module("rows.py").make_chunks(
        9, config["n_features"], rows, n_chunks, config["rows"])
    ref_module = bench.load_module("reference/pca.py")
    ref = ref_module.reference(chunks)
    k = config["params"]["k"]
    control = ref_module.lower_precision_model(chunks, k)
    correct, compared = ref_module.compare([control], ref, spec["limits"])
    assert not correct
    assert compared["ritz_gap"]["value"] > 3 * spec["limits"]["ritz_gap"]
    assert compared["mean_gap"]["value"] > 3 * spec["limits"]["mean_gap"]
    sound = ref_module.lower_precision_model(chunks, k, round_to=None)
    assert ref_module.compare([sound], ref, spec["limits"])[0]


# -- without a chip: no result ------------------------------------------------


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "tpu" in done.stderr
