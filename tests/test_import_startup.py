"""Importing the package does no device work: in a fresh process, ``import
spark_rapids_ml_tpu`` (which imports every estimator and its kernels)
compiles no XLA program, fetches none from the persistent cache and makes
no device array. Every process that fits pays its import before its first
fit, so anything done there — a jit called at module level, a constant put
on the device — is start-up time of every fit path, whatever it fits.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json
import jax
import jax.monitoring

events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **_: events.append(event))
jax.monitoring.register_event_listener(lambda event, **_: events.append(event))
import spark_rapids_ml_tpu  # noqa: E402,F401
from spark_rapids_ml_tpu.models import kmeans, logistic_regression, pca  # noqa
from spark_rapids_ml_tpu.ops import logreg_kernel, streaming  # noqa
print(json.dumps({"events": events, "live_arrays": len(jax.live_arrays())}))
"""


def test_importing_the_package_compiles_nothing_and_makes_no_array():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run([sys.executable, "-c", _PROBE], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr
    seen = json.loads(done.stdout.strip().splitlines()[-1])
    compiles = [e for e in seen["events"]
                if "compile" in e or "compilation_cache" in e]
    assert compiles == []
    assert seen["live_arrays"] == 0
