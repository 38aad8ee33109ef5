"""Fleet federation tour (`spark_rapids_ml_tpu.obs.federation`).

Stands up TWO real serving processes (fitted PCA → registry → engine →
HTTP server, each self-driving a trickle of predict traffic) and runs
the fleet aggregator in THIS process:

1. polls each peer's ``GET /debug/fleet/export`` on a fast cadence and
   merges their series into one host-labeled store — the live table
   printed below is the ``GET /debug/fleet`` rollup document;
2. the Holt forecaster rides the sampler and projects the merged
   queue-wait and request-rate signals, with its own backtest error;
3. a kill drill: SIGKILL peer B, watch ``sparkml_fleet_host_up`` drop
   and the builtin ``fleet_host_down`` detector open ONE incident
   through the standard sampler → detector → incident pipeline, then
   respawn the peer on the same host identity + port and watch the
   incident auto-resolve.

CPU-only example: two serving peers run at once and a chip belongs to
one process, so this process and its peers are pinned to
``JAX_PLATFORMS=cpu``. Run with ``python examples/fleet_example.py``.
"""

import json
import os
import signal  # noqa: F401 - the drill is proc.kill() (SIGKILL)
import socket
import subprocess
import sys
import time
import urllib.request

# runnable from anywhere: put the repo root ahead of the script dir
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ["JAX_PLATFORMS"] = "cpu"
# fast cadences so the demo moves: 100 ms sweeps, 1-sweep incident
# hysteresis (the shipping defaults are 1 s / 3 sweeps)
os.environ["SPARK_RAPIDS_ML_TPU_OBS_SAMPLE_MS"] = "100"
os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_OPEN_AFTER"] = "1"
os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_RESOLVE_AFTER"] = "2"
os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_COOLDOWN_S"] = "0"
os.environ["SPARK_RAPIDS_ML_TPU_OBS_INCIDENT_CAPTURE_S"] = "0"

import numpy as np  # noqa: E402


def peer_main() -> None:
    """Child mode: one self-driving serving process on a fixed port."""
    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.serve import (
        ModelRegistry,
        ServeEngine,
        start_serve_server,
    )

    port = int(os.environ["FLEET_EXAMPLE_PORT"])
    rng = np.random.default_rng(5)
    x = rng.normal(size=(1024, 16))
    registry = ModelRegistry()
    registry.register("fleet_pca", PCA().setK(4).fit(x))
    engine = ServeEngine(registry, max_batch_rows=128, max_wait_ms=2.0,
                         max_queue_depth=256)
    start_serve_server(engine, port=port)
    while True:  # the parent owns this lifetime (SIGKILL)
        n = int(rng.integers(8, 64))
        start = int(rng.integers(0, x.shape[0] - n))
        try:
            engine.predict("fleet_pca", x[start:start + n])
        except Exception:  # noqa: BLE001 - shed under overload is fine
            pass
        time.sleep(0.02)


def free_port() -> int:
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def spawn(host: str, port: int) -> subprocess.Popen:
    env = dict(os.environ)
    env["FLEET_EXAMPLE_PEER"] = "1"
    env["FLEET_EXAMPLE_PORT"] = str(port)
    # a STABLE identity: the respawned peer keeps its host label, so
    # its fleet_host_down incident can auto-resolve
    env["SPARK_RAPIDS_ML_TPU_FLEET_HOST"] = host
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def wait_ready(port: int, timeout_s: float = 90.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2.0)
            return
        except Exception:  # noqa: BLE001 - still booting
            time.sleep(0.2)
    raise RuntimeError(f"peer on :{port} never became ready")


def print_rollup(agg) -> None:
    doc = agg.rollup()
    print(f"  hosts up: {doc['hosts_up']}/{doc['hosts_total']}   "
          f"fleet incidents: {len(doc['fleet_incidents'])}   "
          f"slo burn (5m max): {doc['slo_burn']['max']:.3f}")
    for row in doc["hosts"]:
        staleness = row["staleness_seconds"]
        print(f"    {row['host']:<6} up={str(row['up']):<5} "
              f"stale={staleness if staleness is None else round(staleness, 2)}s "
              f"merged={row['merged_points']} "
              f"replicas={row['replicas']} "
              f"open_incidents={row['open_incidents']}")
    forecast = doc.get("forecast") or {}
    for name, sig in (forecast.get("signals") or {}).items():
        backtest = sig["backtest"]
        print(f"    forecast {name:<14} "
              f"projections={json.dumps(sig['projections'])} "
              f"backtest_rel_err={backtest['rel_err_mean']}")


def main() -> None:
    from spark_rapids_ml_tpu.obs import (
        federation,
        forecast,
        incidents as incidents_mod,
        tsdb as tsdb_mod,
    )

    ports = {"hostA": free_port(), "hostB": free_port()}
    print(f"== spawning 2 serving peers: hostA:{ports['hostA']} "
          f"hostB:{ports['hostB']} (first boot compiles — ~10 s)")
    procs = {host: spawn(host, port) for host, port in ports.items()}
    try:
        for host, port in ports.items():
            wait_ready(port)
        print("== both peers serving; starting the aggregator")

        sampler = tsdb_mod.start_sampling()
        incidents_mod.get_incident_engine().install(sampler)
        forecaster = forecast.get_forecaster()
        forecaster.install(sampler)
        agg = federation.FleetAggregator(
            [(h, f"http://127.0.0.1:{p}") for h, p in ports.items()],
            poll_interval_s=0.25, stale_after_s=1.0,
            forecaster=forecaster)
        federation.set_aggregator(agg)  # /debug/fleet would serve this
        agg.start()

        print("\n== merged fleet view (3 snapshots, 2 s apart)")
        for _ in range(3):
            time.sleep(2.0)
            print_rollup(agg)

        print("\n== kill drill: SIGKILL hostB")
        procs["hostB"].kill()
        procs["hostB"].wait()
        engine = incidents_mod.get_incident_engine()

        def open_fleet_incidents():
            return [i for i in engine.digest()["open"]
                    if i["detector"] == federation.INCIDENT_NAME]

        while not open_fleet_incidents():
            time.sleep(0.2)
        inc = open_fleet_incidents()[0]
        print(f"  incident OPEN: {inc['detector']} "
              f"labels={inc['labels']} reason={inc['reason']!r}")
        print_rollup(agg)

        print("\n== respawning hostB on the same identity + port")
        procs["hostB"] = spawn("hostB", ports["hostB"])
        wait_ready(ports["hostB"])
        while open_fleet_incidents():
            time.sleep(0.2)
        print("  incident RESOLVED (auto — the respawned peer answered "
              "polls under the same host label)")
        print_rollup(agg)

        agg.stop()
        federation.set_aggregator(None)
        print("\n== done")
    finally:
        for proc in procs.values():
            try:
                proc.kill()
            except Exception:  # noqa: BLE001 - already dead is fine
                pass


if __name__ == "__main__":
    if os.environ.get("FLEET_EXAMPLE_PEER") == "1":
        peer_main()
    else:
        main()
