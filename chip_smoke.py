#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the repo's main path once, through the entry points a
user calls, at the full width of the model every chip record so far is
about — PCA at 4096 features, k=256:

    PCA().fit (in-memory matrix, 4 donated Pallas accumulate steps)  →
    PCA().fit (streamed chunks, 16 of them)  →  PCAModel.transform  →  the
    same model behind ServeEngine and the HTTP server (JSON and binary wire)

and checks what comes out against a NumPy float64 oracle, outside any
timing. Weights are whatever the fit finds on seeded random rows. With
more than one device visible it also runs the mesh fits and one serving
replica per chip.

Contract (the driver runs ``python3 chip_smoke.py`` from a checkout of the
commit): exits non-zero — and prints no result line — when JAX's platform
is not ``tpu``, when the device kind is missing from the peaks table, or
when any phase or check fails; on success the LAST stdout line is one JSON
object ``{"ok": true, "device": {...}}``. Sets no JAX_PLATFORMS, starts no
child process (a chip belongs to one process), needs no network beyond
loopback. Phase seconds it prints are smoke output, not performance
claims.

The phases are plain functions so ``tests/test_chip_smoke.py`` can run
them at a toy shape on the CPU; kernel-label checks apply by the platform
JAX reports, there is no switch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import http.client
import json
import sys
import threading
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Shape:
    n_features: int = 4096
    k: int = 256
    # 32768×4096 f32 = 512 MiB, which PCA.fit walks as four views of
    # auto_batch_rows(4096) = 8192 rows: whole batches of the kernel's
    # 1024-row block, so nothing is padded or masked.
    in_memory_rows: int = 32_768
    # the same 8192, tile-aligned → the Pallas accumulate, the one program
    stream_batch_rows: int = 8_192
    # The stream is the in-memory rows cycled this many times: 4 blocks × 4
    # = 16 accumulate steps over 131072 rows. Every block carries the same
    # weight, so the two fits have the same components and variance ratios
    # by construction (the covariances differ by the factor
    # 4(n−1)/(4n−1) only) and a tight agreement bar means something; a
    # dropped or doubled step would unbalance the blocks and rotate the
    # components.
    stream_cycles: int = 4
    serve_max_batch_rows: int = 256
    top: int = 32          # leading components compared vector by vector


FULL = Shape()


class Checks:
    """Measured-against-bar checks. Every check prints its number; a miss
    is recorded and makes the run exit non-zero at the end, so one chip
    call reports every bar that moved instead of the first. Crashes are
    not caught anywhere."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def that(self, name: str, ok: bool, detail: str = "") -> None:
        mark = "ok" if ok else "FAIL"
        log(f"  [{mark}] {name}: {detail}" if detail else f"  [{mark}] {name}")
        if not ok:
            self.failed.append(name)

    def at_most(self, name: str, value: float, bar: float) -> None:
        # inverted comparison so a NaN fails
        self.that(name, bool(value <= bar), f"{value:.3e} (bar {bar:.1e})")


def log(msg: str) -> None:
    print(msg, flush=True)


# -- device -----------------------------------------------------------------


def device_summary() -> dict:
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - the version is a label, not a gate
        libtpu = "unknown"
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu, "numpy": np.__version__},
    }


# -- data and oracle ----------------------------------------------------------


def make_rows(rows: int, n_features: int, seed: int = 0) -> np.ndarray:
    """Seeded float32 rows whose column variances decay as 1/j — the
    spectrum bench.py uses, so the leading components are well separated
    and subspace iteration has something to converge to."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, n_features), dtype=np.float32)
    x *= (1.0 + np.arange(n_features, dtype=np.float32)) ** -0.5
    # a non-zero mean so centering is exercised
    x += rng.standard_normal(n_features, dtype=np.float32) * 0.1
    return x


def oracle_pca(x: np.ndarray, k: int):
    """NumPy float64 reference on the same rows: (components[n,k],
    variance ratios[k], mean[n], covariance[n,n], eigenvalues[n]
    descending), with the repo's own host post-processing (descending
    order, sign convention, λ/Σλ)."""
    from spark_rapids_ml_tpu.ops.eigh import pca_postprocess_host

    x64 = x.astype(np.float64)
    mean = x64.mean(axis=0)
    x64 -= mean
    cov = x64.T @ x64 / (x.shape[0] - 1)
    evals, evecs = np.linalg.eigh(cov)
    pc, evr = pca_postprocess_host(evals, evecs, k)
    return pc, evr, mean, cov, evals[::-1]


# -- trainer ------------------------------------------------------------------


def fit_in_memory(x: np.ndarray, k: int):
    """``fit(matrix)``: the same ``stream_covariance`` over views of ``x``,
    ``batchRows`` left to size itself."""
    from spark_rapids_ml_tpu import PCA

    return PCA().setK(k).fit(x)


def fit_streamed(x: np.ndarray, k: int, batch_rows: int, cycles: int):
    """``fit(factory)``: a zero-argument callable yielding chunks, so the
    estimator runs ``stream_covariance`` — the mean pass, then one donated
    accumulate step per chunk."""
    from spark_rapids_ml_tpu import PCA

    def factory():
        for _ in range(cycles):
            for start in range(0, x.shape[0], batch_rows):
                yield x[start:start + batch_rows]

    return PCA().setK(k).setBatchRows(batch_rows).fit(factory)


def report_fit(checks: Checks, name: str, model, platform: str,
               n_features: int) -> None:
    report = model.fit_report_
    memory = report.memory or {}
    log(f"  {name}: timings {json.dumps(model.fit_timings_)} "
        f"solver {model.svd_solver_used_} "
        f"solve {json.dumps(report.extra.get('solve'))} "
        f"compiles {report.compiles} "
        f"programs {report.programs_compiled}+{report.programs_fetched} "
        f"compile_seconds {report.compile_seconds:.2f} "
        f"wall {report.wall_seconds:.2f}s "
        f"memory {memory.get('source')} peak {memory.get('peak_bytes')}")
    checks.that(f"{name}: fit ran on {platform}",
                report.device_platform == platform,
                f"device_platform={report.device_platform}")
    checks.that(f"{name}: health probe healthy", bool(report.healthy))
    if platform == "tpu":
        checks.that(f"{name}: memory watermark comes from the device",
                    memory.get("source") == "pjrt",
                    f"source={memory.get('source')}")
        gram_bytes = n_features * n_features * 4
        checks.that(f"{name}: peak covers the {gram_bytes >> 20} MiB Gram",
                    (memory.get("peak_bytes") or 0) >= gram_bytes,
                    f"peak_bytes={memory.get('peak_bytes')}")


def report_kernels(checks: Checks, platform: str, in_memory) -> None:
    """Which kernels compiled. On the TPU the Pallas accumulate must have
    compiled, the in-memory fit's Gram steps must all have gone through it
    (it is the streamed loop) and the XLA ``dot_general`` accumulate must
    not have compiled (every batch is full and tile-aligned); both fits'
    solves are one randomized program whose gate passed, so no dense
    ``eigh``; no tracked kernel may have fallen off its AOT executable."""
    from spark_rapids_ml_tpu import obs
    from spark_rapids_ml_tpu.obs.xprof import fallback_signatures

    stats = obs.compile_stats()
    for label, entry in sorted(stats.items()):
        log(f"  compiled {label}: n={entry['compiles']} "
            f"seconds={entry['compile_seconds']:.2f}")

    def compiles(label: str) -> int:
        return stats.get(label, {}).get("compiles", 0)

    calls = in_memory.fit_report_.extra["ingest"]["accumulate_calls"]
    if platform == "tpu":
        checks.that("in-memory fit ran the streamed Pallas accumulate",
                    calls["pallas"] > 0 and calls["xla"] == 0
                    and compiles("_update_centered_gram_fused_blocked") >= 1,
                    json.dumps(calls))
        checks.that("streamed fit compiled the Pallas accumulate",
                    compiles("_update_centered_gram_fused_blocked") >= 1)
        checks.that("XLA Gram accumulate did not compile",
                    compiles("update_centered_gram") == 0
                    and compiles("update_stats") == 0)
        checks.that("the gated solve compiled as one program, and no "
                    "dense eigh beside it",
                    compiles("_randomized_solve_program") == 1
                    and compiles("_dense_solve_program") == 0)
    else:
        checks.that("in-memory fit ran the streamed XLA accumulate",
                    calls["xla"] > 0 and calls["pallas"] == 0,
                    json.dumps(calls))
        checks.that("streamed fit compiled the XLA accumulate",
                    compiles("update_centered_gram") >= 1)
    fallbacks = fallback_signatures()
    checks.that("no tracked kernel fell back off its AOT executable",
                not fallbacks, json.dumps(fallbacks))


# -- correctness (outside any timing) ----------------------------------------


def _aligned_diff(a: np.ndarray, b: np.ndarray) -> float:
    """max |a_j − ±b_j| over columns, sign chosen per column: the sign
    convention pins each vector by its largest entry, and two nearly equal
    largest entries may pick differently at the last bit."""
    sign = np.sign(np.sum(a * b, axis=0))
    return float(np.max(np.abs(a - b * sign)))


def randomized_envelope(evals: np.ndarray, k: int) -> np.ndarray:
    """By how much a variance ratio of the randomized solve may sit under
    the oracle's, component by component, up to a constant of order one:
    Rayleigh-Ritz on the sketch Cov^p·Ω with l columns gives
    θ_j ≥ λ_j / (1 + C·(λ_{l+1}/λ_j)^(2p−1)) (Gu 2015, Thm 4.3, applied
    to Cov^½). l and p are read off the solver's own defaults, so the
    envelope follows the design and a lost iteration shows against it."""
    import inspect

    from spark_rapids_ml_tpu.ops.randomized import (
        randomized_pca_from_covariance,
    )

    defaults = inspect.signature(randomized_pca_from_covariance).parameters
    l = min(k + defaults["oversample"].default, evals.shape[0] - 1)
    p = defaults["n_iter"].default + 1  # the first matvec, then n_iter more
    return (evals[l] / evals[:k]) ** (2 * p - 1)


def check_against_oracle(checks: Checks, model, oracle, shape: Shape,
                         bars: dict) -> None:
    pc_o, evr_o, mean_o, cov_o, evals_o = oracle
    pc = np.asarray(model.pc)
    top = min(shape.top, shape.k)
    checks.at_most("oracle: mean abs error",
                   float(np.max(np.abs(model.mean - mean_o))), bars["mean"])
    checks.at_most("oracle: orthonormality max|VᵀV − I|",
                   float(np.max(np.abs(pc.T @ pc - np.eye(shape.k)))),
                   bars["ortho"])
    checks.at_most(f"oracle: top-{top} components max abs error",
                   _aligned_diff(pc[:, :top], pc_o[:, :top]), bars["pc_top"])
    rel = np.asarray(model.explained_variance) / evr_o - 1.0
    checks.at_most(f"oracle: top-{top} variance ratio relative error",
                   float(np.max(np.abs(rel[:top]))), bars["evr_top"])
    # A Rayleigh-Ritz value never exceeds the eigenvalue it estimates, so
    # at every j whatever sits ABOVE the oracle is rounding.
    checks.at_most(f"oracle: all-{shape.k} variance ratios, excess over the "
                   "oracle", float(np.max(rel)), bars["evr_top"])
    # Below it they may sit by what the solve has not converged: nothing
    # for dense eigh, the envelope times a constant for the randomized one.
    deficit = np.maximum(-rel - bars["evr_top"], 0.0)
    if model.svd_solver_used_ == "randomized":
        checks.at_most(
            f"oracle: all-{shape.k} variance ratios, deficit as a multiple "
            "of the solve's convergence envelope",
            float(np.max(deficit / randomized_envelope(evals_o, shape.k))),
            bars["evr_envelope"])
        log(f"  (largest deficit {float(np.max(-rel)):.3e} at component "
            f"{int(np.argmax(-rel)) + 1})")
    else:
        checks.at_most(f"oracle: all-{shape.k} variance ratios, deficit "
                       "beyond rounding", float(np.max(deficit)), 0.0)
    # How much of the variance the oracle's top-k subspace holds does the
    # fitted subspace hold — the quantity a randomized solve converges in.
    captured = float(np.trace(pc.T @ cov_o @ pc)
                     / (evr_o.sum() * np.trace(cov_o)))
    checks.at_most(f"oracle: variance missed by the k={shape.k} subspace",
                   1.0 - captured, bars["missed"])


def check_fits_agree(checks: Checks, in_memory, streamed, shape: Shape,
                     bars: dict) -> None:
    a, b = np.asarray(in_memory.pc), np.asarray(streamed.pc)
    top = min(shape.top, shape.k)
    checks.at_most(f"streamed vs in-memory: top-{top} components max abs diff",
                   _aligned_diff(a[:, :top], b[:, :top]), bars["pc_top"])
    checks.at_most(
        "streamed vs in-memory: variance ratio relative diff",
        float(np.max(np.abs(np.asarray(streamed.explained_variance)
                            / np.asarray(in_memory.explained_variance) - 1.0))),
        bars["evr_all"])
    overlap = float(np.linalg.norm(a.T @ b) ** 2 / shape.k)
    checks.at_most("streamed vs in-memory: |1 − subspace overlap|",
                   abs(1.0 - overlap), bars["subspace"])
    checks.at_most("streamed vs in-memory: mean abs diff",
                   float(np.max(np.abs(in_memory.mean - streamed.mean))),
                   bars["mean"])


def _relative_error(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check_transform(checks: Checks, model, x: np.ndarray, bar: float) -> None:
    rows = x[:1000]
    out = np.asarray(model.transform(rows).column(model.getOutputCol()))
    want = rows.astype(np.float64) @ model.pc
    checks.that("transform: shape", out.shape == want.shape, str(out.shape))
    checks.at_most("transform: error relative to max|x·pc|",
                   _relative_error(out, want), bar)


# -- server -------------------------------------------------------------------


def _post(port: int, body: bytes, content_type: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/predict", body=body,
                     headers={"Content-Type": content_type})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _total_compiles() -> int:
    from spark_rapids_ml_tpu import obs

    return sum(e["compiles"] for e in obs.compile_stats().values())


def _batches_by_device(metric: str, model_name: str) -> dict:
    """``{device label: batches}`` from the live metrics registry."""
    from spark_rapids_ml_tpu.obs import get_registry

    family = get_registry().snapshot().get(metric, {"samples": []})
    return {s["labels"]["device"]: s["value"] for s in family["samples"]
            if s["labels"].get("model") == model_name}


def serve_requests(checks: Checks, model, x: np.ndarray, shape: Shape,
                   platform: str, n_devices: int, bar: float) -> dict:
    """Register → warm up → real HTTP: mixed-size JSON requests, one
    binary-wire request, and (several devices) a burst of concurrent
    requests over one replica per device."""
    import jax

    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        start_serve_server,
        wire,
    )

    name = "pca_smoke"
    registry = ModelRegistry()
    registry.register(name, model)
    engine = ServeEngine(registry, max_batch_rows=shape.serve_max_batch_rows,
                         max_wait_ms=2.0, replicas=n_devices)
    server = None
    try:
        t0 = time.perf_counter()
        warm = engine.warmup(name)
        log(f"  warm-up: buckets {sorted(warm['buckets'])} in "
            f"{time.perf_counter() - t0:.2f}s")
        compiles_warm = _total_compiles()
        server = start_serve_server(engine, port=0)
        port = server.server_address[1]

        def expect(rows: np.ndarray) -> np.ndarray:
            return rows.astype(np.float64) @ model.pc

        def check_answer(label: str, status: int, outputs, degraded, retries,
                         rows: np.ndarray) -> None:
            checks.that(f"{label}: 200", status == 200, f"status={status}")
            if status != 200:
                return
            checks.that(f"{label}: not degraded, no retries",
                        degraded is False and retries == 0,
                        f"degraded={degraded} retries={retries}")
            checks.at_most(f"{label}: error relative to max|x·pc|",
                           _relative_error(np.asarray(outputs), expect(rows)),
                           bar)

        def post_json(rows: np.ndarray):
            body = json.dumps({"model": name, "rows": rows.tolist()}).encode()
            status, _, raw = _post(port, body, "application/json")
            return status, json.loads(raw)

        top_rows = shape.serve_max_batch_rows
        for n in (1, top_rows // 7 + 1, top_rows):  # 1, 37, 256 at full size
            rows = x[100:100 + n]
            status, doc = post_json(rows)
            check_answer(f"JSON {n} rows", status, doc.get("outputs"),
                         doc.get("degraded"), doc.get("retries"), rows)

        # One request above max_batch_rows. A single replica cannot hold it
        # and the engine says so (400, "split it"); with several devices it
        # is served by the batch-sharded program over all of them.
        n_over = top_rows + top_rows // 4
        rows = x[:n_over]
        status, doc = post_json(rows)
        if n_devices == 1:
            checks.that(f"JSON {n_over} rows (> max_batch_rows): refused "
                        "with 400 on one device", status == 400,
                        f"status={status} {str(doc.get('error'))[:80]}")
        else:
            check_answer(f"JSON {n_over} rows (sharded over {n_devices})",
                         status, doc.get("outputs"), doc.get("degraded"),
                         doc.get("retries"), rows)

        def post_binary(rows: np.ndarray):
            """(status, outputs, degraded, retries) over the columnar wire;
            the response's metadata travels as headers."""
            status, headers, raw = _post(
                port, wire.encode_request(name, rows),
                wire.BINARY_CONTENT_TYPE)
            if status != 200:
                return status, None, None, None
            return (status, wire.decode_response(raw),
                    headers.get("X-Degraded") != "0",
                    int(headers.get("X-Retries", -1)))

        rows = x[500:500 + min(100, top_rows)]
        check_answer(f"binary wire {rows.shape[0]} rows", *post_binary(rows),
                     rows)

        if n_devices > 1:
            # Sizes above a quarter of max_batch_rows: smaller requests are
            # concentrated onto the first lightly loaded replica by design
            # (serve/placement.py), these are placed least-loaded with a
            # rotating tie-break, so every replica must see some.
            sizes = (top_rows // 4 + 1, top_rows // 2, top_rows)
            errors: list = []

            def worker(i: int) -> None:
                chunk = x[i * 16:i * 16 + sizes[i % 3]]
                try:
                    status, outputs, degraded, retries = post_binary(chunk)
                except Exception as exc:  # noqa: BLE001 - reported below
                    errors.append((i, repr(exc)))
                    return
                if status != 200 or degraded or retries:
                    errors.append((i, status, degraded, retries))
                elif _relative_error(outputs, expect(chunk)) > bar:
                    errors.append((i, "outputs differ"))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(48)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            checks.that("48 concurrent requests: all right, none degraded",
                        not errors and not any(t.is_alive() for t in threads),
                        str(errors[:3]))

        checks.that("zero compiles after warm-up",
                    _total_compiles() == compiles_warm,
                    f"{_total_compiles() - compiles_warm} new")
        status, raw = _get(port, "/readyz")
        checks.that("/readyz ready", status == 200
                    and json.loads(raw).get("ready") is True,
                    f"status={status} {raw[:120]!r}")

        # One replica has no replica tier: its batches are attributed to
        # the device by the device monitor. Several replicas each count
        # their own (the sharded request above touches every device in the
        # monitor's series, so that one cannot show the spread).
        metric = ("sparkml_serve_replica_batches_total" if n_devices > 1
                  else "sparkml_serve_device_batches_total")
        batches = _batches_by_device(metric, name)
        log(f"  {metric} by device label: {json.dumps(batches)}")
        labels = {str(d) for d in jax.devices()[:n_devices]}
        checks.that("every device served batches, under its own label",
                    set(batches) == labels
                    and all(v > 0 for v in batches.values()),
                    f"expected labels {sorted(labels)}")
        if platform == "tpu":
            checks.that("the device label names a TPU",
                        all("TPU" in label.upper() for label in batches))
        # The label has parentheses, commas and '=' on a TPU; make sure the
        # exporter and a /debug query carry it whole.
        status, raw = _get(port, "/metrics")
        text = raw.decode()
        checks.that("/metrics carries the device label",
                    status == 200 and all(
                        f'device="{label}"' in text for label in batches))
        status, raw = _get(
            port, f"/debug/history?name={metric}")
        checks.that("/debug/history answers for the labelled series",
                    status == 200 and isinstance(json.loads(raw), dict),
                    f"status={status}")
        return batches
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        engine.shutdown()


# -- the fence ----------------------------------------------------------------


def fence_check(checks: Checks, x: np.ndarray, shape: Shape,
                kind: str) -> None:
    """Is ``block_until_ready`` a completion fence here? Times one burst of
    production accumulate steps three ways: no fence (dispatch only),
    ``block_until_ready``, and a host read of a Gram element. The two
    fenced times must agree and neither may beat the time the chip needs
    for the useful FLOPs at peak — an early return would."""
    import jax

    from spark_rapids_ml_tpu.ops.streaming import init_stats, update_stats_auto
    from spark_rapids_ml_tpu.utils.platform import PEAK_FLOPS_BF16

    device = jax.devices()[0]
    batch = jax.device_put(x[:shape.stream_batch_rows], device)
    steps = 16

    def burst(fence) -> float:
        stats = init_stats(shape.n_features, device=device)
        jax.block_until_ready(stats)
        t0 = time.perf_counter()
        for _ in range(steps):
            stats = update_stats_auto(stats, batch)
        fence(stats)
        seconds = time.perf_counter() - t0
        jax.block_until_ready(stats)
        return seconds

    def read_element(stats) -> float:
        return float(stats.gram[0, 0])

    burst(jax.block_until_ready)  # compiles the step …
    burst(read_element)           # … and the one-element read
    dispatch = burst(lambda stats: None)
    blocked = burst(jax.block_until_ready)
    host_read = burst(read_element)
    # the folded kernel does half of 2·rows·n² multiply-adds per step
    floor = steps * shape.stream_batch_rows * shape.n_features ** 2 \
        / PEAK_FLOPS_BF16[kind]
    log(f"  {steps} accumulate steps: dispatch only {dispatch * 1e3:.2f} ms, "
        f"block_until_ready {blocked * 1e3:.2f} ms, host read "
        f"{host_read * 1e3:.2f} ms, peak-FLOPs floor {floor * 1e3:.2f} ms")
    checks.that("block_until_ready waits for the device",
                blocked >= floor, f"{blocked * 1e3:.2f} ms vs floor")
    checks.at_most("block_until_ready and a host read agree (relative)",
                   abs(blocked - host_read) / max(blocked, host_read), 0.2)


# -- several chips ------------------------------------------------------------


def multichip(checks: Checks, x: np.ndarray, in_memory, shape: Shape,
              n_devices: int, bars: dict) -> None:
    """The fits over all the chips, each checked for an even split of the
    rows and against the one-chip fit: the streamed fit — the one loop
    (``ops.streaming.stream_covariance``) with whole batches dealt to the
    chips in turn, the Pallas Gram on every chip, two all-reduces and the
    solve's one program on the first chip — and both all-reduce schedules of
    the one-shot mesh program (XLA Gram under shard_map, not the Pallas
    kernel).

    The eigensolve is part of each one-shot mesh program, and the dense
    4096² ``eigh`` compiles for ≈4.5 min on this jax/libtpu: three default
    solves took 838 of the 922 s the first four-chip run needed, against
    the driver's 1200 (PR 21). So the default solver — what a caller who
    passes nothing gets — runs once, on the two-pass schedule and last;
    the other two take ``solver="randomized"``, the solver the one-chip
    fit they are compared with chose for itself."""
    from spark_rapids_ml_tpu.data.batches import BatchSource
    from spark_rapids_ml_tpu.parallel import data_mesh, distributed_pca_fit
    from spark_rapids_ml_tpu.parallel.streaming import (
        distributed_streaming_pca_fit,
    )

    # As ordered below, on four v5e chips with a cold cache: 63 + 57 + 289 s
    # for this phase, 491 s for the whole script (PR 21). The first of those
    # was the streamed fit as a mesh program with the solve compiled into it
    # (one jitted program with ten 266² eigh calls), which it no longer is:
    # through the shared loop this check takes 7.5 s the first time (the
    # per-chip programs' first dispatch; the solve's programs came with
    # the one-chip fit before it) and 0.11 s the second (PR 28, this
    # check alone on four chips). The one-pass mesh program still compiles
    # its solve (57 s).
    mesh = data_mesh(n_devices)
    want_rows = x.shape[0] // n_devices
    top = min(shape.top, shape.k)
    ref = np.asarray(in_memory.pc)[:, :top]

    def check(name: str, result) -> None:
        report = result.fit_report_
        rows = report.extra.get("rows_per_device", {})
        log(f"  {name}: wall {report.wall_seconds:.2f}s rows per device "
            f"{json.dumps(rows)}")
        checks.that(f"{name}: each of {n_devices} devices holds 1/{n_devices} "
                    "of the rows", len(rows) == n_devices
                    and set(rows.values()) == {want_rows})
        checks.at_most(f"{name}: top-{top} components vs one chip",
                       _aligned_diff(np.asarray(result.components)[:, :top],
                                     ref), bars["pc_top"])

    check("mesh fit, streamed, randomized", distributed_streaming_pca_fit(
        BatchSource(x, batch_rows=shape.stream_batch_rows), shape.k, mesh,
        solver="randomized"))
    check("mesh fit, one-pass, randomized", distributed_pca_fit(
        x, shape.k, mesh, one_pass=True, solver="randomized"))
    check("mesh fit, two-pass, default solver (dense eigh)",
          distributed_pca_fit(x, shape.k, mesh))


# -- main ---------------------------------------------------------------------

# Bars for the full shape: 2.7 to 4.5 times what this script measured on
# one TPU v5e (jax 0.9.0, libtpu 0.0.34; PR 21) — each measurement stands
# next to its bar. Rows, solver seed and kernels are all seeded, and three
# runs gave identical numbers, so the margin is for another libtpu, not
# for noise. They replace the bars of the July notes (|pc − oracle| ≲
# 5e-5, taken at 256 features and k=8).
ORACLE_BARS = {
    # float32 column sums of 32768 rows; measured 5.5e-8
    "mean": 2e-7,
    # two eigh-whitening passes in float32; measured 1.2e-5
    "ortho": 5e-5,
    # float32 Gram (bfloat16_3x) over eigen-gaps of λ₁/1024 at j=32: the
    # rounding of the covariance is amplified by 1/gap; measured 5.8e-4
    "pc_top": 2e-3,
    # Rayleigh-Ritz values are second-order in the vector error; 2.5e-6
    # over the top 32, and 2.8e-6 for the largest excess over all 256
    "evr_top": 1e-5,
    # the randomized solve runs 4 power iterations with 10 spare columns,
    # so components near j=k have not converged and their Ritz values sit
    # low — 9.3e-2 low at j=256, the solver's design point (ROADMAP S6),
    # not rounding. An absolute bar on that cannot tell 4 iterations from
    # 3 (1.3e-1), so the deficit is held against the design's own
    # convergence envelope (randomized_envelope): measured 0.55 of it on
    # the chip, at j=100; one iteration fewer gives 4.6 (CPU float32 on
    # the same rows, which reproduces the chip's 0.55 and its tail).
    "evr_envelope": 1.5,
    # for the same reason the fitted subspace misses a sliver of what the
    # oracle's top-256 hold; measured 2.2e-3
    "missed": 6e-3,
}
AGREE_BARS = {
    "mean": 4e-7,        # measured 8.9e-8
    "pc_top": 2e-3,      # two float32 roundings of one covariance; 5.2e-4
    "evr_all": 2e-5,     # same solver, same seed, same subspace; 4.6e-6
    "subspace": 1e-5,    # measured |1e-6| (float32 floor of the norm)
}
# x·pc on the MXU at Precision.HIGHEST against float64 on the host,
# relative to max|x·pc|: transform 4.5e-7, served answers ≤ 3.9e-7 — the
# July "≲ 1e-6" holds.
PROJECTION_BAR = 2e-6


def main() -> int:
    from spark_rapids_ml_tpu.utils.platform import (
        PEAK_FLOPS_BF16,
        configure_compile_cache,
    )

    # The driver allows 1200 s. A hang — a chip another process holds, a
    # wedged collective — should end as a failure with every thread's stack
    # on stderr, not as a kill that says nothing.
    faulthandler.dump_traceback_later(1100, exit=True)
    t_start = time.perf_counter()
    cache_dir = configure_compile_cache()
    device = device_summary()
    log(f"platform: {device['platform']}")
    log(f"device_kind: {device['kind']}")
    log(f"device_count: {device['count']}")
    log(f"versions: {json.dumps(device['versions'])}")
    log(f"compile cache: {cache_dir}")
    if device["platform"] != "tpu":
        log(f"chip_smoke: needs a TPU, JAX found platform "
            f"{device['platform']!r}")
        return 1
    if device["kind"] not in PEAK_FLOPS_BF16:
        log(f"chip_smoke: device kind {device['kind']!r} is not in the peaks "
            "table (utils/platform.py)")
        return 1

    shape = FULL
    checks = Checks()
    seconds: dict = {}

    @contextlib.contextmanager
    def phase(name: str):
        log(f"== {name}")
        t0 = time.perf_counter()
        yield
        seconds[name] = round(time.perf_counter() - t0, 2)

    with phase("rows"):
        x = make_rows(shape.in_memory_rows, shape.n_features)

    with phase("fit in-memory"):
        in_memory = fit_in_memory(x, shape.k)
    report_fit(checks, "in-memory", in_memory, "tpu", shape.n_features)

    with phase("fit streamed"):
        streamed = fit_streamed(x, shape.k, shape.stream_batch_rows,
                                shape.stream_cycles)
    report_fit(checks, "streamed", streamed, "tpu", shape.n_features)
    report_kernels(checks, "tpu", in_memory)

    with phase("oracle (NumPy float64, host)"):
        oracle = oracle_pca(x, shape.k)
    with phase("correctness"):
        check_against_oracle(checks, in_memory, oracle, shape, ORACLE_BARS)
        check_fits_agree(checks, in_memory, streamed, shape, AGREE_BARS)
        check_transform(checks, in_memory, x, PROJECTION_BAR)

    with phase("serve"):
        serve_requests(checks, in_memory, x, shape, "tpu", device["count"],
                       PROJECTION_BAR)

    with phase("fence"):
        fence_check(checks, x, shape, device["kind"])

    if device["count"] > 1:
        with phase(f"multichip ({device['count']} devices)"):
            multichip(checks, x, in_memory, shape, device["count"],
                      AGREE_BARS)
    else:
        log("multichip: skipped (1 device)")

    from spark_rapids_ml_tpu import obs

    total_compile = sum(e["compile_seconds"]
                        for e in obs.compile_stats().values())
    log(f"phase seconds: {json.dumps(seconds)}")
    log(f"tracked compile seconds (whole run): {total_compile:.2f}")
    log(f"total seconds: {time.perf_counter() - t_start:.1f}")
    if checks.failed:
        log(f"chip_smoke: {len(checks.failed)} check(s) failed:")
        for name in checks.failed:
            log(f"  - {name}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
