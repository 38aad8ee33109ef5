"""Second-algorithm chip throughput: KMeans, LogisticRegression,
RandomForest (BASELINE.md config 5).

Prints one JSON line per model:
``{"metric", "value", "unit", "config", "seconds", "util"}`` where
``util`` is the useful-FLOPs fraction of the chip's bf16 peak for the
models whose FLOP count is clean (KMeans assignment, LogReg Hessian);
RandomForest's histogram contractions depend on live-node occupancy, so
it reports ``null`` rather than a made-up number.

Methodology matches bench.py: on-device synthetic data, compile excluded
by a warm-up run, a host read of the result as the completion fence. Run
directly (``python bench_models.py``) in the one process that owns the
chip.

Env knobs: BMODELS_ROWS, BMODELS_COLS (shared by all three workloads).
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


def main() -> None:
    import jax
    import jax.numpy as jnp

    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        # the metrics below are per-chip rates: no CPU leg
        raise SystemExit(
            f"bench_models.py needs a TPU, JAX found {device.platform!r}")
    if device.device_kind not in _PEAK_FLOPS_BF16:
        raise SystemExit(
            f"bench_models.py: device kind {device.device_kind!r} is not in "
            f"the peaks table (utils/platform.py)")
    peak = _PEAK_FLOPS_BF16[device.device_kind]

    rows = int(os.environ.get("BMODELS_ROWS", 2_097_152))
    cols = int(os.environ.get("BMODELS_COLS", 64))
    key = jax.random.PRNGKey(0)
    x = jax.device_put(
        jax.random.normal(key, (rows, cols), dtype=jnp.float32), device
    )

    def fence(v):
        return np.asarray(v).ravel()[0]

    results = []

    # -- KMeans: Lloyd iterations ---------------------------------------
    from spark_rapids_ml_tpu.ops.kmeans_kernel import (
        kmeans_fit_kernel,
        kmeans_plus_plus_init,
    )

    k = 64
    iters = 10
    init = kmeans_plus_plus_init(x, k, jax.random.PRNGKey(1))
    fence(kmeans_fit_kernel(x, init, max_iter=iters, tol=0.0).centers)
    t0 = time.perf_counter()
    r = kmeans_fit_kernel(x, init, max_iter=iters, tol=0.0)
    fence(r.centers)
    dt = time.perf_counter() - t0
    it_done = int(np.asarray(r.n_iter))
    km_rows = rows * max(it_done, 1) / dt
    km_flops = 2.0 * rows * cols * k * max(it_done, 1)
    results.append({
        "metric": "KMeans Lloyd rows/sec/chip",
        "value": round(km_rows, 1),
        "unit": "rows/sec (per Lloyd pass)",
        "config": f"{rows}x{cols} k={k} iters={it_done}",
        "seconds": round(dt, 3),
        "util": round(km_flops / dt / peak, 4),
    })

    # -- LogisticRegression: Newton-IRLS --------------------------------
    from spark_rapids_ml_tpu.ops.logreg_kernel import logreg_fit_kernel

    w_true = jax.random.normal(jax.random.PRNGKey(2), (cols,),
                               dtype=jnp.float32)
    y = (x @ w_true > 0).astype(jnp.float32)
    n_iter_cfg = 8
    fence(logreg_fit_kernel(x, y, None, reg_param=1e-3,
                            max_iter=n_iter_cfg, tol=0.0).coefficients)
    t0 = time.perf_counter()
    r = logreg_fit_kernel(x, y, None, reg_param=1e-3,
                          max_iter=n_iter_cfg, tol=0.0)
    fence(r.coefficients)
    dt = time.perf_counter() - t0
    it_done = int(np.asarray(r.n_iter))
    lr_rows = rows * max(it_done, 1) / dt
    # per iteration: XᵀWX (2nd²) + Xw, Xᵀr, Xᵀs (≈6nd)
    lr_flops = (2.0 * rows * cols * cols + 6.0 * rows * cols) * max(
        it_done, 1
    )
    results.append({
        "metric": "LogisticRegression Newton rows/sec/chip",
        "value": round(lr_rows, 1),
        "unit": "rows/sec (per Newton pass)",
        "config": f"{rows}x{cols} iters={it_done}",
        "seconds": round(dt, 3),
        "util": round(lr_flops / dt / peak, 4),
    })

    # -- RandomForest: histogram trees ----------------------------------
    from spark_rapids_ml_tpu import RandomForestClassifier

    rf_rows = min(rows, 524_288)
    x_rf = np.asarray(x[:rf_rows], dtype=np.float32)
    y_rf = np.asarray(y[:rf_rows], dtype=np.float64)
    est = (
        RandomForestClassifier().setNumTrees(16).setMaxDepth(8)
        .setSeed(7)
    )
    est.fit(x_rf, y_rf)   # warm-up at the timed shape (compiles excluded)
    t0 = time.perf_counter()
    model = est.fit(x_rf, y_rf)
    dt = time.perf_counter() - t0
    assert model is not None
    results.append({
        "metric": "RandomForest fit rows/sec/chip",
        "value": round(rf_rows / dt, 1),
        "unit": "rows/sec (16 trees, depth 8, end-to-end fit)",
        "config": f"{rf_rows}x{cols} trees=16 depth=8",
        "seconds": round(dt, 3),
        "util": None,
    })

    for row in results:
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
