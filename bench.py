"""Benchmark: PCA.fit throughput on the chip, with achieved MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Needs a TPU: with any other platform it exits non-zero and prints no
number (ROADMAP S1 replaces this script with the cell matrix).

Measures PCA fit over 10M×4096 rows, k=256, f32 by default, via the
streaming sufficient-statistics pipeline — bounded HBM: one batch + one
4096² Gram resident; batches stream through the MXU with donated
accumulators. ``platform``/``device_kind``/``measured_rows`` fields carry
the run's circumstances. ``mfu`` is useful-FLOPs MFU: 2·rows·cols² for the
Gram over the chip's peak — with the default ``bfloat16_3x`` Gram
precision the MXU does 3 bf16 passes per useful FLOP, so ~33% is the
ceiling for a full Gram; the Pallas symmetric folded-grid kernel computes
only the upper triangle (half the passes), raising the attainable ceiling
to ~67%.

The reference publishes no numbers (SURVEY.md §6), so ``vs_baseline`` is
the speedup over the host-CPU oracle path (NumPy/LAPACK), projected from a
subsample — the "accelerated vs CPU Spark ML" comparison its tests imply.

Env knobs: BENCH_ROWS, BENCH_COLS, BENCH_K, BENCH_BATCH, BENCH_CPU_ROWS,
BENCH_MAX_SECONDS.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from spark_rapids_ml_tpu.utils.platform import (
    PEAK_FLOPS_BF16 as _PEAK_FLOPS_BF16,
    configure_compile_cache,
)


def _emit_record(record: dict) -> None:
    """Final-line emission through the ONE shared helper (embeds the
    metrics-registry snapshot); falls back to a bare JSON line if the
    scripts/ package is unreachable (e.g. bench.py copied elsewhere)."""
    import sys

    scripts_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"
    )
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    try:
        from bench_common import emit_record

        emit_record(record)
    except Exception:  # noqa: BLE001 - the bench number must still print
        print(json.dumps(record))


def main() -> None:
    # Default workload is BASELINE.md config 4 (the north star, per chip):
    # 10M×4096 k=256. The finalize is a fixed cost that more rows amortize;
    # the north-star row count measures the steady state the metric is
    # defined on.
    rows = int(os.environ.get("BENCH_ROWS", 10_485_760))
    cols = int(os.environ.get("BENCH_COLS", 4096))
    k = int(os.environ.get("BENCH_K", 256))
    batch = int(os.environ.get("BENCH_BATCH", 65536))
    cpu_rows = int(os.environ.get("BENCH_CPU_ROWS", 100_000))
    max_seconds = float(os.environ.get("BENCH_MAX_SECONDS", 1200))

    import jax

    configure_compile_cache()
    device = jax.devices()[0]
    platform = device.platform
    device_kind = str(device.device_kind)
    if platform != "tpu":
        # one process owns the chip, so there is no probe child and no CPU
        # leg: a number from another backend would carry the device
        # metric's name
        raise SystemExit(f"bench.py needs a TPU, JAX found {platform!r}")
    if device_kind not in _PEAK_FLOPS_BF16:
        raise SystemExit(
            f"bench.py: device kind {device_kind!r} is not in the peaks "
            f"table (utils/platform.py)")

    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.streaming import (
        finalize_stats,
        init_stats,
        update_stats,
        update_stats_auto,
    )

    # On-device synthetic batch: the bench measures the fit pipeline (Gram
    # accumulation + eigensolve), not host data generation. Per-feature
    # variances decay as a power law — the spectral regime PCA is used in.
    # Plain isotropic randn has NO principal structure: its near-flat
    # spectrum (wishart spread ±2√(n/rows) ≈ ±0.04, further broadened by
    # the bf16_3x Gram's quantization noise) gives subspace iteration
    # nothing to converge to, and the residual gate correctly refuses the
    # randomized finalize there — measured resid/scale 0.019 on a clean
    # synthetic wishart vs >0.05 through the accumulated pipeline.
    key = jax.random.PRNGKey(0)
    col_scale = (1.0 + jnp.arange(cols, dtype=jnp.float32)) ** -0.5
    x_batch = jax.device_put(
        jax.random.normal(key, (batch, cols), dtype=jnp.float32)
        * col_scale[None, :],
        device,
    )
    n_steps = max(1, rows // batch)
    configured_rows = n_steps * batch

    # warm-up: compile update + finalize once.
    # update_stats_auto is the PRODUCTION accumulate: on TPU with aligned
    # f32 batches it selects the Pallas symmetric folded-grid Gram (half
    # the MXU/HBM work), elsewhere the XLA dot_general path.
    stats = init_stats(cols, dtype=jnp.float32, device=device)
    stats = update_stats_auto(stats, x_batch)
    np.asarray(finalize_stats(stats, k).components)

    # Timed run, in flushes of up to 16 queued steps. Each flush ends with a
    # host read of the scalar row count as the fence (block_until_ready
    # fences equally on this chip — chip_smoke.py times both). The flush
    # cadence also enforces BENCH_MAX_SECONDS: a slow run is truncated and
    # says so instead of hanging.
    stats = init_stats(cols, dtype=jnp.float32, device=device)
    flush = 16
    steps_done = 0
    t0 = time.perf_counter()
    while steps_done < n_steps:
        burst = min(flush, n_steps - steps_done)
        for _ in range(burst):
            stats = update_stats_auto(stats, x_batch)
        int(np.asarray(stats.count))  # fence
        steps_done += burst
        if time.perf_counter() - t0 > max_seconds:
            break
    accumulate_seconds = time.perf_counter() - t0
    measured_rows = steps_done * batch
    truncated = measured_rows < configured_rows

    # Headline finalize: svdSolver='auto' through the residual gate
    # (randomized O(n²k) subspace iteration when k ≪ n, verified on device
    # with ‖Cov·V − V·Λ‖, dense-eigh fallback on gate failure) — the
    # production default since round 3. Warm-up compiles BOTH the
    # randomized solve and its gate read so the timed number is
    # steady-state, matching how the accumulate phase is timed.
    from spark_rapids_ml_tpu.ops.eigh import pca_from_covariance_gated
    from spark_rapids_ml_tpu.ops.streaming import covariance_from_stats

    warm = pca_from_covariance_gated(
        covariance_from_stats(stats.gram, stats.col_sum, stats.count), k
    )
    np.asarray(warm[0])
    # (the gated warm-up above runs on the IDENTICAL covariance, so it
    # already compiled exactly the branch — randomized, or the dense-eigh
    # fallback if the gate trips — that the timed call will take)
    t0 = time.perf_counter()
    cov = covariance_from_stats(stats.gram, stats.col_sum, stats.count)
    pc, evr, solver_used = pca_from_covariance_gated(cov, k)
    components_host = np.asarray(pc)  # fence (model → host)
    finalize_seconds = time.perf_counter() - t0
    assert np.isfinite(components_host).all()

    # secondary arm: the dense full-spectrum eigh finalize
    # (svdSolver='eigh', exact per-vector parity path). Recorded so every
    # round keeps the auto-vs-eigh evidence.
    r = finalize_stats(stats, k, solver="eigh")
    np.asarray(r.components)  # compile + fence
    t0 = time.perf_counter()
    r = finalize_stats(stats, k, solver="eigh")
    rc = np.asarray(r.components)
    finalize_eigh_seconds = round(time.perf_counter() - t0, 3)
    assert np.isfinite(rc).all()

    fit_seconds = accumulate_seconds + finalize_seconds
    rows_per_sec = measured_rows / fit_seconds

    useful_flops = 2.0 * measured_rows * cols * cols
    mfu = round(useful_flops / fit_seconds / _PEAK_FLOPS_BF16[device_kind], 4)

    # A/B arms: steady-state rate of each Gram accumulator
    # (update_stats_auto above encodes the winner; these fields keep the
    # evidence in every record). An arm that fails to compile or run fails
    # the bench — a Pallas kernel Mosaic refuses must not print one '#' line.
    pallas_rows_per_sec = None
    xla_rows_per_sec = None
    if os.environ.get("BENCH_COMPARE_PALLAS", "1") == "1":
        from spark_rapids_ml_tpu.ops.streaming import (
            _update_stats_fused_blocked,
            accumulate_path,
        )

        def _arm_rate(step_fn):
            astats = init_stats(cols, dtype=jnp.float32, device=device)
            astats = step_fn(astats, x_batch)  # compile
            int(np.asarray(astats.count))
            asteps = min(32, n_steps)
            astats = init_stats(cols, dtype=jnp.float32, device=device)
            t0 = time.perf_counter()
            for _ in range(asteps):
                astats = step_fn(astats, x_batch)
            int(np.asarray(astats.count))  # fence
            return round(asteps * batch / (time.perf_counter() - t0), 1)

        probe_stats = init_stats(cols, dtype=jnp.float32, device=device)
        if accumulate_path(probe_stats.gram, x_batch, None) == "pallas":
            pallas_rows_per_sec = _arm_rate(_update_stats_fused_blocked)
        else:
            print("# pallas gram arm skipped: shape not applicable "
                  "(the Pallas Gram needs tile-aligned f32 batches)",
                  flush=True)
        xla_rows_per_sec = _arm_rate(update_stats)

    # CPU baseline proxy: same pipeline via NumPy/LAPACK. The per-row Gram
    # cost is measured on a subsample and scaled to the full row count; the
    # one-off eigh cost is measured once and added unscaled — so the
    # projected full-size CPU run amortizes its eigensolve over ALL rows,
    # exactly like the accelerator measurement does.
    x_cpu = np.asarray(x_batch[: min(cpu_rows, batch)], dtype=np.float64)
    reps = max(1, cpu_rows // x_cpu.shape[0])
    t0 = time.perf_counter()
    g = np.zeros((cols, cols))
    s = np.zeros(cols)
    for _ in range(reps):
        g += x_cpu.T @ x_cpu
        s += x_cpu.sum(axis=0)
    gram_seconds = time.perf_counter() - t0
    n = reps * x_cpu.shape[0]
    mu = s / n
    cov = (g - n * np.outer(mu, mu)) / (n - 1)
    t0 = time.perf_counter()
    np.linalg.eigh(cov)
    eigh_seconds = time.perf_counter() - t0
    cpu_seconds_projected = gram_seconds * (measured_rows / n) + eigh_seconds
    cpu_rows_per_sec = measured_rows / cpu_seconds_projected

    record = {
        "metric": f"PCA.fit rows/sec/chip ({configured_rows}x{cols}, k={k})",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        "vs_baseline": round(rows_per_sec / cpu_rows_per_sec, 2),
        "platform": platform,
        "device_kind": device_kind,
        "measured_rows": measured_rows,
        "truncated": truncated,
        "mfu": mfu,
        "fit_seconds": round(fit_seconds, 2),
        "finalize_seconds": round(finalize_seconds, 3),
        "finalize_solver": solver_used,
        "finalize_eigh_seconds": finalize_eigh_seconds,
        "pallas_rows_per_sec": pallas_rows_per_sec,
        "xla_rows_per_sec": xla_rows_per_sec,
    }
    _emit_record(record)


if __name__ == "__main__":
    main()
