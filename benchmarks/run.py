#!/usr/bin/env python3
"""The benchmark's runner: one cell, one run, one result line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is data or a small file found by name
from ``BENCHMARK.json``: the configuration (``configs/<config>.json``: the
estimator, its Params, the rows recipe, and the names of its reference and
its work), the traffic mix (``traffic/<traffic>.json``: how the host
chunks are handed to ``fit``), the cell's limits (``cells/<workload>.json``),
the plain reference (``reference/<reference>.py``: ``model_of``, which
takes from a fitted model what is compared, ``reference``, ``compare`` and
the planted ``FAULTS`` of its tests), the algorithm's work
(``work/<work>.py``: ``flops``, ``least_seconds`` and, where a trace can
name them, the ``PROGRAMS`` that do it) and one reader per per-layer
metric (``metrics/<metric>.py``). This file names none of them, and no
field of any fitted model.

A run: make the rows from ``--seed`` (set-up), fit once to warm every
program (set-up), then fit back to back for ``--seconds`` — a fit that has
started runs to its end — and only then, with the peak memory read, run
the reference and compare every model the window produced with it. The
last stdout line is the result; the numbers compared, each beside its
limit, are the last lines of stderr and the result's last key.

Exits 2 and prints no result when JAX's platform is not ``tpu``, when
fewer chips are there than the cell asks for, or when the device kind is
missing from ``peaks.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIT_SPAN = "bench_fit"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(relpath: str):
    """A file of the benchmark, by its path under ``benchmarks/``."""
    path = os.path.join(HERE, relpath)
    name = "bench_" + relpath.replace(os.sep, "_").replace(".", "_").replace(
        "-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, home: str = "") -> dict:
    """Everything the cell is made of, resolved by name. ``home`` is the
    directory under ``benchmarks/`` that holds the traffic, cells,
    reference and work files, and a ``fixtures.json`` in the form of
    ``BENCHMARK.json`` where it is not the benchmark's (the tests')."""
    bench = read_json(os.path.join(HERE, home, "fixtures.json") if home
                      else os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    config = read_json(os.path.join(ROOT, entry["file"]))
    if not config.get("work"):
        raise SystemExit(f"configuration {entry['name']!r} names no work "
                         f"module (\"work\": <name of work/<name>.py>)")
    # the deployment's settings of the TPU runtime; libtpu reads them when
    # JAX first touches the chip, so they are set before anything does
    for key, value in config.get("runtime_env", {}).items():
        os.environ.setdefault(key, str(value))
    return {
        "cell": cell,
        "config": config,
        "home": home,
        "traffic": read_json(os.path.join(
            HERE, home, "traffic", cell["traffic"] + ".json")),
        "limits": read_json(os.path.join(
            HERE, home, "cells", workload + ".json"))["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def find_chips(chips: int) -> tuple:
    """(devices, peaks entry) — or exit 2 with no result."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"benchmark needs platform 'tpu', JAX reports {platform!r}")
        raise SystemExit(2)
    if len(devices) < chips:
        log(f"cell asks for {chips} chips, JAX sees {len(devices)}")
        raise SystemExit(2)
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        log(f"device kind {kind!r} is not in benchmarks/peaks.json")
        raise SystemExit(2)
    return devices[:chips], peaks[kind]


def configure_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where set), keeping every program
    however quick its compile: the eager solve is dozens of small ones."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts every program JAX compiled or fetched from its cache,
    tracked by the program or not (``jax.monitoring``)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1


def make_estimator(config: dict, overrides: dict = None):
    module_name, _, cls_name = config["estimator"].partition(":")
    cls = getattr(importlib.import_module(module_name), cls_name)
    est = cls()
    for name, value in {**config["params"], **(overrides or {})}.items():
        est.set(name, value)
    return est


def chunk_shape(config: dict, traffic: dict) -> tuple:
    """(rows per chunk, chunks per fit) of a configuration under a mix."""
    rows = traffic["chunk_rows"]
    if isinstance(rows, str):  # the name of a key of the configuration
        rows = config[rows]
    return int(rows), int(traffic["chunks_per_fit"])


def dataset_factory(traffic: dict, chunks: list):
    """A zero-argument callable giving what one ``fit`` call is handed."""
    form = traffic["input_form"]
    if form == "callable":
        return lambda: (lambda: list(chunks))
    if form == "iterator":
        return lambda: iter(chunks)
    raise SystemExit(f"unknown input_form {form!r}")


def reference_of(config: dict, home: str = ""):
    """The configuration's plain reference module."""
    return load_module(os.path.join(home, "reference",
                                    config["reference"] + ".py"))


def work_of(config: dict, home: str = ""):
    """The configuration's work module, bound to the configuration where
    the module asks for it (``bind(config)``: work that depends on a
    Param as well as on rows and features)."""
    work = load_module(os.path.join(home, "work", config["work"] + ".py"))
    return work.bind(config) if hasattr(work, "bind") else work


def fit_once(config: dict, new_dataset, overrides: dict = None,
             model_of=None) -> dict:
    """One ``fit`` from host chunks to a model on the host, timed: the
    wall holds the reference's ``model_of``, which fetches what is
    compared."""
    import jax

    model_of = model_of or reference_of(config).model_of
    est = make_estimator(config, overrides)
    dataset = new_dataset()
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(FIT_SPAN):
        fitted = est.fit(dataset)
        model = model_of(fitted)
    end = time.perf_counter()
    return {"start": start, "end": end, "wall": end - start,
            "timings": dict(getattr(fitted, "fit_timings_", None) or {}),
            "solver": getattr(fitted, "svd_solver_used_", None),
            "model": model}


def run_window(config: dict, new_dataset, seconds: float,
               model_of=None) -> tuple:
    """Fits back to back until ``seconds`` have passed; a fit that has
    started runs to its end. (fits, failures)."""
    fits, failed = [], 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        try:
            fits.append(fit_once(config, new_dataset, model_of=model_of))
        except Exception:  # noqa: BLE001 - counted, reported, not hidden
            failed += 1
            log(traceback.format_exc())
            if failed >= 3:
                break
    return fits, failed


def device_summary(devices, trace_info: dict = None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace_info and trace_info["busy_s"] is not None:
        out["busy_s"] = trace_info["busy_s"]
        out["window_s"] = trace_info["window_s"]
    return out


def reduce_trace(trace_dir: str) -> dict:
    xplane = load_module("xplane.py")
    t = time.perf_counter()
    path = xplane.find_xplane(trace_dir)
    planes = xplane.load(path)
    log(f"trace: {os.path.getsize(path) >> 20} MiB, "
        f"{sum(len(l['events']) for p in planes for l in p['lines'])} "
        f"events read in {time.perf_counter() - t:.2f}s")
    span = xplane.window(planes, FIT_SPAN)
    if span is None:
        raise RuntimeError(f"no {FIT_SPAN!r} span in the trace")
    lo, hi = span
    state = xplane.busy(planes, lo, hi)
    return {"planes": planes, "lo": lo, "hi": hi,
            # None without a device plane (a rehearsal on the CPU)
            "busy_s": state["busy_s"] if state["chips"] else None,
            "window_s": (hi - lo) / 1e9,
            "breakdown": {
                "device_ops": xplane.top_device_ops(planes, lo, hi),
                "idle_gaps": xplane.idle_gaps(planes, lo, hi)}}


def read_per_layer(spec: dict, ctx: dict) -> dict:
    metrics = {}
    for m in spec["per_layer"]:
        value = load_module(os.path.join("metrics", m["name"] + ".py")).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True) -> dict:
    spec = load_spec(workload)
    config, traffic = spec["config"], spec["traffic"]
    home = spec.get("home", "")
    import jax

    if require_chip:
        devices, peak = find_chips(spec["cell"]["chips"])
        configure_cache()
    else:  # the tests' door: everything below runs the same
        devices, peak = jax.devices()[:1], None
    compiles = CompileCounter()
    make_estimator(config)  # a checkout without the program fails here
    log(f"start: imports and the chip in {time.perf_counter() - T_START:.2f}s")

    chunk_rows, n_chunks = chunk_shape(config, traffic)
    n = int(config["n_features"])
    t = time.perf_counter()
    chunks = load_module("rows.py").make_chunks(
        seed, n, chunk_rows, n_chunks, config["rows"], devices[0])
    log(f"rows: {n_chunks} chunks of {chunk_rows}x{n} float32 in "
        f"{time.perf_counter() - t:.2f}s")
    new_dataset = dataset_factory(traffic, chunks)

    ref_module, work = reference_of(config, home), work_of(config, home)
    warm = fit_once(config, new_dataset, model_of=ref_module.model_of)
    log(f"warm-up fit: {warm['wall']:.2f}s {json.dumps(warm['timings'])} "
        f"solver={warm['solver']} compiles={compiles.count}")

    trace_dir = os.path.join(ROOT, ".bench_out", "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles_before = compiles.count
    window_start = time.perf_counter()
    setup_s = window_start - T_START
    fits, failed = run_window(config, new_dataset, seconds,
                              model_of=ref_module.model_of)
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - compiles_before
    if not fits:
        raise SystemExit("no fit completed in the window")
    window_s = fits[-1]["end"] - window_start
    rows_per_fit = chunk_rows * n_chunks
    slowest = max(fits, key=lambda f: f["wall"])
    log(f"window: {len(fits)} fits in {window_s:.3f}s, "
        f"{compiles_in_window} compiles, walls "
        f"{[round(f['wall'], 3) for f in fits]}; slowest "
        f"{json.dumps(slowest['timings'])}")

    t = time.perf_counter()
    trace_info = reduce_trace(trace_dir) if trace else None
    if trace:
        log(f"trace reduced in {time.perf_counter() - t:.2f}s")
    device = device_summary(devices, trace_info)

    # only now the reference: the peak above is the fits' own
    t = time.perf_counter()
    ref = ref_module.reference(chunks, devices[0])
    correct, compared = ref_module.compare(
        [f["model"] for f in fits], ref, spec["limits"])
    correct = correct and failed == 0
    log(f"reference and comparison: {time.perf_counter() - t:.2f}s")

    if trace:
        metrics = read_per_layer(spec, {
            "fits": fits, "window_s": window_s, "rows_per_fit": rows_per_fit,
            "n_features": n, "chips": len(devices),
            "work": work,
            # the static count; the program's own, where its reports have
            # it, is ``work/crossing.py`` ``window_ingest``
            "bytes_put_per_fit": traffic["crossings"] * rows_per_fit * n * 4,
            "first_fit_s": warm["wall"], "setup_s": setup_s,
            "compiles_in_window": compiles_in_window, "trace": trace_info,
            "peak": peak, "load_module": load_module})
    else:
        values = {"fit_rows_per_s": len(fits) * rows_per_fit / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}

    result = {"correct": correct, "attempted": len(fits) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if trace_info:
        result["breakdown"] = trace_info["breakdown"]
    result["workload"] = workload
    result["seed"] = seed
    result["fits"] = len(fits)
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} = {c['value']:.6e} limit {c['limit']}")
    log(f"correct = {correct}")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)  # the system under test
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
