"""Tour of the unified telemetry subsystem (`spark_rapids_ml_tpu.obs`).

Runs a PCA estimator fit and a distributed PCA fit with trace export
enabled, then shows the observability surfaces:

1. ``fit_report_`` — the uniform per-fit artifact (phases, mesh,
   collectives, health), now including the XLA compile story (compile
   wall-clock, recompile count, HLO cost-analysis FLOPs, per-phase
   analytic MFU) and the device-memory watermark;
2. Chrome-trace JSON files written under ``SPARK_RAPIDS_ML_TPU_TRACE_DIR``
   (load them in Perfetto / chrome://tracing);
3. the process metrics registry, as Prometheus text and over HTTP;
4. the flight recorder: a watchdog dump of thread stacks / open spans /
   metrics under ``SPARK_RAPIDS_ML_TPU_DUMP_DIR`` when a phase overruns
   its budget;
5. the serving tier: ``transform_report_`` per transform/predict call
   (rows, bytes, device-put/compute/host-sync split, compile
   attribution, numerics-sentinel verdict) and the live sketch-backed
   p50/p95/p99 latency per algo.

CPU-safe: run with ``python examples/observability_example.py``.
"""

import glob
import json
import os
import sys
import tempfile
import urllib.request

# runnable from anywhere: put the repo root ahead of the script dir
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "")
     + " --xla_force_host_platform_device_count=8").strip(),
)
trace_dir = tempfile.mkdtemp(prefix="sparkml_traces_")
os.environ["SPARK_RAPIDS_ML_TPU_TRACE_DIR"] = trace_dir

import numpy as np  # noqa: E402

from spark_rapids_ml_tpu import PCA, obs  # noqa: E402
from spark_rapids_ml_tpu.parallel import (  # noqa: E402
    data_mesh,
    distributed_pca_fit,
)


def main() -> None:
    rng = np.random.default_rng(7)
    x = rng.normal(size=(512, 16))

    # -- 1. per-fit reports ------------------------------------------------
    model = PCA().setK(4).fit(x)
    report = model.fit_report_
    print("== estimator fit_report_")
    print(f"  algo={report.algo}  rows={report.rows}  "
          f"platform={report.device_platform}  healthy={report.healthy}")
    print(f"  phases: { {k: round(v, 4) for k, v in report.phases.items()} }")
    print("== compile report (obs.xprof via tracked_jit)")
    print(f"  compiles={report.compiles}  recompiles={report.recompiles}  "
          f"compile_seconds={report.compile_seconds:.3f}")
    print(f"  programs_compiled={report.programs_compiled}  "
          f"programs_fetched={report.programs_fetched}  (every executable "
          "JAX built, the eager solve's too)")
    agg = obs.compile_stats()
    for label in sorted(agg)[:4]:
        s = agg[label]
        print(f"  {label}: {s['compiles']} compile(s), "
              f"{s['compile_seconds']:.3f}s")
    print("== device-memory watermark (obs.memory)")
    print(f"  peak_device_bytes={report.peak_device_bytes}  "
          f"source={(report.memory or {}).get('source')}")
    wm = obs.memory_watermarks()
    print(f"  live watermark: {wm['peak_bytes']} bytes "
          f"({wm['source']}; host RSS {wm['host_peak_rss_bytes']})")

    mesh = data_mesh()
    res = distributed_pca_fit(x, 4, mesh)
    dreport = res.fit_report_
    print("== distributed driver fit_report_")
    print(f"  mesh={dreport.mesh_shape} axes={dreport.mesh_axes}")
    print(f"  collectives: {dreport.collectives}")
    print(f"  total collective bytes: {dreport.total_collective_bytes()}")
    print("  as JSON:", json.dumps(dreport.as_dict(), default=str)[:160],
          "...")

    # -- 2. exported Chrome traces ----------------------------------------
    files = sorted(glob.glob(os.path.join(trace_dir, "*.json")))
    print(f"== {len(files)} Chrome-trace file(s) in {trace_dir}")
    doc = json.load(open(files[0]))
    names = [e["name"] for e in doc["traceEvents"]]
    print(f"  {os.path.basename(files[0])}: spans {names}")
    print("  open in https://ui.perfetto.dev or chrome://tracing")

    # -- 3. the metrics registry ------------------------------------------
    registry = obs.get_registry()
    print("== Prometheus text exposition (excerpt)")
    for line in registry.prometheus_text().splitlines():
        if "sparkml_fits_total" in line or "collective_bytes" in line:
            print(" ", line)

    server = obs.start_prometheus_server(port=0)
    port = server.server_address[1]
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    print(f"== scraped {len(body)} bytes from http://127.0.0.1:{port}/metrics")
    server.shutdown()
    server.server_close()

    # -- 4. the flight recorder -------------------------------------------
    import time

    dump_dir = tempfile.mkdtemp(prefix="sparkml_dumps_")
    os.environ["SPARK_RAPIDS_ML_TPU_DUMP_DIR"] = dump_dir
    with obs.deadline("example_stalled_phase", budget_seconds=0.2):
        time.sleep(0.8)  # overruns the budget -> watchdog dumps
    deadline_t = time.monotonic() + 5.0
    dumps = []
    while not dumps and time.monotonic() < deadline_t:
        dumps = sorted(glob.glob(os.path.join(dump_dir,
                                              "flightdump_*.json")))
        time.sleep(0.05)
    print(f"== {len(dumps)} flight dump(s) in {dump_dir}")
    if dumps:
        doc = json.load(open(dumps[0]))
        print(f"  reason={doc['reason']}  "
              f"threads={len(doc['thread_stacks'])}  "
              f"open_spans={[s['name'] for s in doc['open_spans']]}")

    # -- 5. serving observability -----------------------------------------
    print("== serving tier: TransformReport per transform/predict call")
    for batch in range(30):
        batch_rows = x[(batch * 16) % 256:][:64]
        out = model.transform(batch_rows)
    treport = model.transform_report_
    print(f"  algo={treport.algo}  rows={treport.rows}  "
          f"bytes_in={treport.bytes_in}  bytes_out={treport.bytes_out}")
    print("  phase split:",
          {k: round(v, 5) for k, v in treport.phases.items()})
    print(f"  compiles={treport.compiles} (first call pays the XLA "
          f"compile; later batches hit the cache)")
    print(f"  numerics sentinel: {treport.numerics}")
    print("  report rides on the output too:",
          type(out).__name__, hasattr(out, "transform_report_"))
    live = obs.latency_quantiles("pca")
    print(f"  live sketch-backed latency: p50={live['p50']:.5f}s  "
          f"p95={live['p95']:.5f}s  p99={live['p99']:.5f}s")
    print("  as Prometheus summary lines:")
    for line in obs.get_registry().prometheus_text().splitlines():
        if "sparkml_transform_latency_seconds{" in line:
            print("   ", line)


if __name__ == "__main__":
    main()
