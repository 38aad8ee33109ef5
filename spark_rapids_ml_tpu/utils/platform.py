"""What the benches and the chip smoke need to know about the device: the
peaks table, and where JAX's persistent compilation cache goes.

Nothing here runs on import, and the package never configures the cache
itself — entry points (``chip_smoke.py``, the benches) call
``configure_compile_cache`` before their first compile.
"""

from __future__ import annotations

import os

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``. Only kinds a
# chip run of this repo has OBSERVED are listed: "TPU v5 lite" is what
# jax 0.9.0 / libtpu 0.0.34 report for one TPU v5e chip (chip_smoke.py,
# PR 21). Source of both numbers: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM per chip). A kind missing from the
# table reports None — never a made-up number — and is an error in the
# chip smoke and the benchmark.
PEAK_FLOPS_BF16 = {
    "TPU v5 lite": 197e12,
}

# The roofline's second axis (bytes/s of HBM). A step whose arithmetic
# intensity (FLOPs / bytes accessed) sits below the ridge point
# ``peak_flops / peak_bw`` is memory-bound; above it, compute-bound.
PEAK_HBM_BYTES_PER_SECOND = {
    "TPU v5 lite": 819e9,
}

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# The cache directory is part of what a later run must find again, so it
# is ONE fixed path inside the checkout (git-ignored) — never a tempfile,
# pid or time-derived name.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is configured here. Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. Call before the first compile;
    ``tracked_jit``'s AOT ``lower().compile()`` goes through this cache
    like every ``jax.jit``.
    """
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
