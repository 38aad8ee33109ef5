"""Shared helpers for the bench family: the status log and the single
JSON-emission path.

``emit_record`` is the ONE way every bench (bench.py, bench_scale.py,
bench_gram_sweep.py, bench_serve.py, the harnesses) emits its final JSON
line — it stamps the record and embeds a metrics-registry snapshot, so
per-fit collective/phase accounting rides along with every bench number
instead of each script hand-rolling ``json.dumps``.
"""

from __future__ import annotations

import datetime
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def stamp() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ")


def log(msg: str) -> None:
    """One status line on stderr — a bench run writes nothing into the
    checkout (stdout stays the record channel)."""
    sys.stderr.write(f"{msg}: {stamp()}\n")
    sys.stderr.flush()


def force_device_count_flags(n_devices: int, env: dict = None) -> str:
    """The ``XLA_FLAGS`` value a subprocess child needs to see
    ``n_devices`` forced host devices, preserving every other flag the
    parent environment carries (device count is fixed at jax init, so
    multi-device-count benches spawn one child per count). Shared by
    bench_serve's multidevice scenario, load_harness's device-scaling
    phase, and chaos_drill's replica_drain phase — one copy of the
    flag-splicing logic."""
    source = os.environ if env is None else env
    kept = [f for f in source.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    kept.append(f"--xla_force_host_platform_device_count={n_devices}")
    return " ".join(kept)


def prefixed_result(stdout: str, prefix: str):
    """The machine-readable child-result line a subprocess leg printed
    (``PREFIX {json}``), parsed — or None when the child never emitted
    one (the caller reports rc/stderr)."""
    line = next((ln for ln in (stdout or "").splitlines()
                 if ln.startswith(prefix)), None)
    if line is None:
        return None
    return json.loads(line[len(prefix):])


_REQUIRE_PLATFORM_ENV = "SPARKML_BENCH_REQUIRE_PLATFORM"


def backend_provenance() -> dict:
    """The RESOLVED jax backend (not the requested one): platform,
    device kind, device count. {} when jax is unavailable — provenance
    must never fail a bench. Callers on the emit path have already
    initialized the backend, so this never triggers a fresh init cost."""
    try:
        import jax

        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", None),
            "device_count": len(devices),
        }
    except Exception:  # noqa: BLE001 - provenance must never fail a bench
        return {}


def required_platform() -> str | None:
    """The platform this bench run REQUIRES (``SPARKML_BENCH_REQUIRE_
    PLATFORM=tpu``), or None when any resolved backend is acceptable."""
    value = os.environ.get(_REQUIRE_PLATFORM_ENV, "").strip().lower()
    return value or None


def enforce_required_platform(provenance: dict | None = None) -> dict:
    """Refuse to continue when the resolved backend is not the required
    one — a record measured on a silent CPU fallback is worse than no
    record. Exits with code 3. Returns the provenance when the check
    passes."""
    want = required_platform()
    prov = provenance if provenance is not None else backend_provenance()
    if want is None:
        return prov
    got = (prov.get("platform") or "").lower()
    if got != want:
        log(f"backend mismatch: required {want}, resolved {got or 'none'}")
        flight_dump("bench_backend_mismatch", required=want,
                    resolved=got or None)
        print(json.dumps({
            "error": "backend_mismatch",
            "required_platform": want,
            "resolved_platform": got or None,
        }), flush=True)
        raise SystemExit(3)
    return prov


def metrics_snapshot() -> dict:
    """The process metrics registry as a JSON-safe dict ({} when the
    package (or its telemetry) is unavailable — emission never fails)."""
    try:
        from spark_rapids_ml_tpu.obs import get_registry

        return get_registry().snapshot()
    except Exception:  # noqa: BLE001 - emission must never fail
        return {}


def emit_record(record: dict, *, stream=None, include_metrics: bool = True,
                flush: bool = True) -> dict:
    """Emit one bench record as a single JSON line (the LAST stdout
    line). Stamps ``emitted_utc`` and embeds
    the metrics-registry snapshot under ``"metrics"``. Returns the emitted
    dict. ``stream=None`` prints to stdout; pass an open file to append to
    a record file instead."""
    rec = dict(record)
    rec.setdefault("emitted_utc", stamp())
    if "backend" not in rec:
        # every record names the backend it was measured on — the
        # perf sentinel compares records only within one backend and
        # flags cross-backend drift as backend_mismatch, not regression
        prov = backend_provenance()
        if prov:
            rec["backend"] = prov
        want = required_platform()
        if want is not None:
            rec["required_platform"] = want
            enforce_required_platform(prov)
    if include_metrics and "metrics" not in rec:
        snap = metrics_snapshot()
        if snap:
            rec["metrics"] = snap
    line = json.dumps(rec)
    if stream is None:
        print(line, flush=flush)
    else:
        stream.write(line + "\n")
        if flush:
            stream.flush()
    return rec


def flight_dump(reason: str, **extra) -> str | None:
    """Flight-recorder dump, guarded: a wedge produces a diagnostic
    artifact (thread stacks, spans, metrics, cached health) in
    ``SPARK_RAPIDS_ML_TPU_DUMP_DIR``, never a bench failure."""
    try:
        from spark_rapids_ml_tpu.obs import flight

        return flight.dump(reason, extra=extra or None)
    except Exception:  # noqa: BLE001 - dumps must never break a bench
        return None
