#!/usr/bin/env python3
"""Readings for the limits: the compared numbers over many seeds, for the
program as configured and for its lower-precision controls, in one process.

    python3 benchmarks/sweep.py --workloads a,b --seeds 11,12,13 --controls 3

The workloads have to share a configuration: each seed's rows and
reference are made once and every workload fits them. For the first
``--controls`` seeds the program also fits with the configuration's
``control`` Params (its own lower-precision path), and the reference is
put in the program's place with its rows rounded to bfloat16. No window is
timed: one fit per reading, through the same ``fit_once`` the runner uses.
One JSON line per reading on stdout, a summary (largest sound, smallest
control) last. ``--rows key=value`` overrides a key of the rows recipe
(to read what a recipe does, never for a committed limit).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402


def faults(config, new_dataset, sound_fit, ref):
    """Planted faults, each of which one compared number has to read: the
    randomized solve with one power iteration fewer (where the solve is
    the randomized one); the sound model with its last component swapped
    for a random unit vector; and with its first component swapped for
    one and that component's explained variance made consistent with the
    new vector, so that only the subspace's number can tell."""
    import numpy as np

    if sound_fit["solver"] == "randomized":
        import inspect
        from functools import partial

        from spark_rapids_ml_tpu.ops import randomized

        real = randomized.randomized_pca_from_covariance
        n_iter = inspect.signature(real).parameters["n_iter"].default
        randomized.randomized_pca_from_covariance = partial(
            real, n_iter=n_iter - 1)
        try:
            fit = bench.fit_once(config, new_dataset)
        finally:
            randomized.randomized_pca_from_covariance = real
        yield (f"fault_iterations_{n_iter - 1}_{fit['solver']}",
               fit["model"], fit["wall"])
    n = sound_fit["model"]["pc"].shape[0]
    v = np.random.default_rng(0).standard_normal(n)
    v /= np.linalg.norm(v)
    model = {k: np.array(a) for k, a in sound_fit["model"].items()}
    model["pc"][:, -1] = v
    yield "fault_last_component_swapped", model, 0.0
    model = {k: np.array(a) for k, a in sound_fit["model"].items()}
    model["pc"][:, 0] = v
    model["explained_variance"][0] = v @ ref["cov"] @ v / ref["trace"]
    yield "fault_top_component_lost", model, 0.0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workloads", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=0)
    p.add_argument("--rows", action="append", default=[])
    p.add_argument("--faults", action="store_true",
                   help="on the control seeds also read the planted faults")
    p.add_argument("--cpu-ok", action="store_true",
                   help="rehearsal: skip the look for a chip")
    args = p.parse_args(argv)
    specs = {w: bench.load_spec(w) for w in args.workloads.split(",")}
    first = next(iter(specs.values()))
    config = first["config"]
    if any(s["config"]["name"] != config["name"] for s in specs.values()):
        raise SystemExit("the workloads have to share a configuration")
    for item in args.rows:
        key, _, value = item.partition("=")
        config["rows"][key] = float(value)
    import jax

    if args.cpu_ok:
        device = jax.devices()[0]
    else:
        device = bench.find_chips(1)[0][0]
        bench.configure_cache()
    ref_module = bench.load_module(os.path.join(
        "reference", config["reference"] + ".py"))
    chunk_rows, n_chunks = bench.chunk_shape(config, first["traffic"])
    k = config["params"]["k"]
    readings = []

    def emit(seed, workload, kind, gaps, seconds):
        row = {"seed": seed, "workload": workload, "kind": kind,
               "seconds": round(seconds, 3), **gaps}
        readings.append(row)
        print(json.dumps(row), flush=True)

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        chunks = bench.load_module("rows.py").make_chunks(
            seed, config["n_features"], chunk_rows, n_chunks, config["rows"],
            device)
        t = time.perf_counter()
        ref = ref_module.reference(chunks, device)
        bench.log(f"seed {seed}: reference {time.perf_counter() - t:.2f}s")
        for workload, spec in specs.items():
            new_dataset = bench.dataset_factory(spec["traffic"], chunks)
            sound = bench.fit_once(config, new_dataset)
            emit(seed, workload, "sound",
                 ref_module.gaps(sound["model"], ref), sound["wall"])
            if i < args.controls:
                fit = bench.fit_once(config, new_dataset,
                                     config["control"]["params"])
                emit(seed, workload, "control_program",
                     ref_module.gaps(fit["model"], ref), fit["wall"])
            if i < args.controls and args.faults:
                for kind, model, wall in faults(config, new_dataset, sound,
                                                 ref):
                    emit(seed, workload, kind, ref_module.gaps(model, ref),
                         wall)
        if i < args.controls:
            t = time.perf_counter()
            model = ref_module.lower_precision_model(chunks, k, device)
            emit(seed, "-", "control_reference", ref_module.gaps(model, ref),
                 time.perf_counter() - t)
        del chunks, ref

    names = ("mean_gap", "ritz_gap", "miss_gap")
    summary = {}
    for workload in list(specs) + ["-"]:
        for kind in sorted({r["kind"] for r in readings}):
            rows = [r for r in readings
                    if r["workload"] == workload and r["kind"] == kind]
            if rows:
                pick = max if kind == "sound" else min
                summary[f"{workload}/{kind}/n={len(rows)}"] = {
                    n: pick(r[n] for r in rows) for n in names}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
