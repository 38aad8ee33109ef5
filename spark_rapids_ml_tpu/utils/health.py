"""Device health checks at task start.

The reference's failure posture is "let a CUDA error kill the task and let
Spark reschedule" (SURVEY.md §5: ``env->ThrowNew`` / executor-killing
asserts, ``rapidsml_jni.cu:115,189,356-358``). The TPU-native posture keeps
kernels side-effect-free (safe to re-execute) and adds what the reference
lacked: an explicit runtime health probe before work is scheduled, so a
device backend hang or fault fails fast with a diagnosis instead of
hanging a fit.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class DeviceHealth:
    healthy: bool
    platform: str
    device_count: int
    probe_seconds: float
    error: Optional[str] = None
    devices: List[str] = field(default_factory=list)


def check_devices(probe_all: bool = True) -> DeviceHealth:
    """Run a tiny compiled op on the runtime (optionally every local
    device); returns a structured verdict instead of raising.

    No timeout here: backend init itself can block on a hung device
    backend, and an in-process deadline can't preempt it.
    """
    t0 = time.perf_counter()
    try:
        import jax
        import jax.numpy as jnp

        devices = jax.devices()
        names = []
        targets = devices if probe_all else devices[:1]
        for d in targets:
            out = jax.device_put(jnp.ones((8, 8)), d).sum()
            if float(out) != 64.0:
                raise RuntimeError(f"bad probe result on {d}: {out}")
            names.append(str(d))
        return DeviceHealth(
            healthy=True,
            platform=devices[0].platform,
            device_count=len(devices),
            probe_seconds=time.perf_counter() - t0,
            devices=names,
        )
    except Exception as e:  # noqa: BLE001 - health checks report, not raise
        return DeviceHealth(
            healthy=False,
            platform="unknown",
            device_count=0,
            probe_seconds=time.perf_counter() - t0,
            error=f"{type(e).__name__}: {e}",
        )


def check_devices_subprocess(timeout_seconds: float = 90.0) -> DeviceHealth:
    """Health probe with a hard wall-clock bound: runs in a child process so
    a hanging backend init cannot wedge the caller.

    Only for a parent that has NOT touched JAX: an accelerator belongs to
    one process, so from a process that already holds the chip the child
    can never get it and this probe reports unhealthy (or times out).
    Nothing on the chip path calls it — fits use the in-process
    ``check_devices``."""
    import json
    import subprocess
    import sys

    # The child's stdout is a parsed protocol (last line = the verdict
    # JSON), written directly — not print, not a logger (a log line is
    # ALSO JSON and could be mistaken for the verdict).
    code = (
        "import json, sys\n"
        "from spark_rapids_ml_tpu.utils.health import check_devices\n"
        "h = check_devices()\n"
        "sys.stdout.write(json.dumps(h.__dict__) + chr(10))\n"
    )
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_seconds,
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        if proc.returncode == 0 and line.startswith("{"):
            return DeviceHealth(**json.loads(line))
        return DeviceHealth(
            healthy=False,
            platform="unknown",
            device_count=0,
            probe_seconds=time.perf_counter() - t0,
            error=f"probe exited rc={proc.returncode}: {proc.stderr[-300:]}",
        )
    except subprocess.TimeoutExpired:
        return DeviceHealth(
            healthy=False,
            platform="unknown",
            device_count=0,
            probe_seconds=time.perf_counter() - t0,
            error=f"backend init exceeded {timeout_seconds}s (device backend hung?)",
        )
