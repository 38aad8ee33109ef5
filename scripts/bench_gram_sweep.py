"""Gram-accumulator arm sweep on the chip.

Sweeps the Pallas symmetric folded-grid kernel over (block_n, block_r)
shapes and MXU precision arms against the steady-state donated-accumulator
workload bench.py times (65536×4096 f32 batches), plus the XLA
``dot_general`` reference arm. Prints one JSON line per arm and a final
summary line naming the winner — committed records decide whether the
production constants (_BLOCK_N/_BLOCK_R, bfloat16_3x) move.

Precision arms: ``bfloat16_3x`` (production: 2-limb split, 3 MXU passes,
~f32 covariance), ``default`` (single bf16 pass — the throughput ceiling,
~3× fewer MXU passes at bf16 accuracy; recorded to quantify the
speed/precision trade users opt into via TPUML_GRAM_PRECISION).
"""

from __future__ import annotations

import json
import os
import time

from bench_common import emit_record

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.utils.platform import (
        PEAK_FLOPS_BF16,
        configure_compile_cache,
    )

    configure_compile_cache()
    device = jax.devices()[0]
    platform = device.platform
    if platform != "tpu":
        raise SystemExit(
            f"gram sweep: the Pallas kernel is TPU-only, found {platform!r}")

    from spark_rapids_ml_tpu.ops.pallas_gram import fused_centered_gram

    rows = int(os.environ.get("GSWEEP_ROWS", 65536))
    cols = int(os.environ.get("GSWEEP_COLS", 4096))
    steps = int(os.environ.get("GSWEEP_STEPS", 24))
    key = jax.random.PRNGKey(0)
    col_scale = (1.0 + jnp.arange(cols, dtype=jnp.float32)) ** -0.5
    x = jax.device_put(
        jax.random.normal(key, (rows, cols), dtype=jnp.float32)
        * col_scale[None, :],
        device,
    )
    zero_mean = jnp.zeros((cols,), dtype=jnp.float32)
    ones = jnp.ones((rows,), dtype=jnp.float32)
    peak = PEAK_FLOPS_BF16.get(
        str(getattr(device, "device_kind", platform))
    )

    shapes = [(512, 1024), (512, 2048), (1024, 1024), (1024, 2048),
              (256, 1024), (512, 512)]
    precisions = ["bfloat16_3x", "default"]
    results = []

    def record(name, rate, extra=None):
        useful = 2.0 * rows * cols * cols  # full-Gram useful FLOPs
        rec = {
            "metric": f"gram accumulate rows/sec ({rows}x{cols})",
            "arm": name,
            "value": rate,
            "unit": "rows/sec",
            "platform": platform,
            "mfu": (round(useful * rate / rows / peak, 4)
                    if peak else None),
        }
        if extra:
            rec.update(extra)
        results.append(rec)
        emit_record(rec, include_metrics=False)

    def time_arm(fn):
        acc = jnp.zeros((cols, cols), dtype=jnp.float32)
        acc = acc + fn()  # compile
        float(np.asarray(acc[0, 0]))  # fence (host read)
        acc = jnp.zeros((cols, cols), dtype=jnp.float32)
        t0 = time.perf_counter()
        for _ in range(steps):
            acc = acc + fn()
        float(np.asarray(acc[0, 0]))
        return round(steps * rows / (time.perf_counter() - t0), 1)

    for bn, br in shapes:
        for prec in precisions:
            name = f"pallas_{bn}x{br}_{prec}"
            try:
                rate = time_arm(lambda: fused_centered_gram(
                    x, zero_mean, ones, precision=prec,
                    block_n=bn, block_r=br,
                ))
            except Exception as exc:  # noqa: BLE001 - arm must not kill sweep
                print(json.dumps({
                    "arm": name, "error": f"{type(exc).__name__}: {exc}"[:300]
                }), flush=True)
                continue
            record(name, rate)

    # XLA reference arms
    for prec_name, prec in (
        ("bf16_3x", jax.lax.Precision.HIGH),
        ("bf16", jax.lax.Precision.DEFAULT),
    ):
        def xla_gram(p=prec):
            return jax.lax.dot_general(
                x, x, (((0,), (0,)), ((), ())), precision=p,
                preferred_element_type=jnp.float32,
            )

        record(f"xla_dot_general_{prec_name}", time_arm(xla_gram))

    # Winners are per-PRECISION: arms at different precisions do different
    # MXU work (default = 1 bf16 pass, bfloat16_3x = 3), so a global max
    # would always name a single-pass arm and say nothing about the
    # question the sweep decides — which block shape the production
    # bfloat16_3x constants (_BLOCK_N/_BLOCK_R) should carry.
    for prec in ("bfloat16_3x", "default"):
        arms = [r for r in results if r["arm"].endswith(prec)
                or (prec == "bfloat16_3x" and r["arm"].endswith("bf16_3x"))
                or (prec == "default" and r["arm"].endswith("_bf16"))]
        if not arms:
            continue
        best = max(arms, key=lambda r: r["value"])
        emit_record({
            "metric": f"gram sweep winner ({prec})",
            "decides": ("production _BLOCK_N/_BLOCK_R"
                        if prec == "bfloat16_3x"
                        else "single-pass bf16 ceiling (opt-in precision)"),
            "arm": best["arm"],
            "value": best["value"],
            "unit": "rows/sec",
            "mfu": best["mfu"],
            "rows": rows, "cols": cols, "steps": steps,
        })


if __name__ == "__main__":
    main()
