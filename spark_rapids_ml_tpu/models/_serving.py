"""Shared ``ServingProgram`` construction for the pipelined serving hook,
plus the **fused whole-pipeline** composition layer.

Every model exposing ``serving_transform_program`` needs the same
scaffolding: resolve the device and transform dtype, decide whether the
donated kernel twin is worth using (donation is a warning no-op on CPU),
look up the precision variant, stage the constant model weights to the
device ONCE, and wrap the put / run / fetch closures into an
``obs.serving.ServingProgram``. This module holds that scaffolding so
PCA / KMeans / LogisticRegression (and future models) each contribute
only what is genuinely theirs: the kernel table and the per-precision
weight staging.

Weight staging happens here exactly once per program: the bf16 variants
receive pre-cast weights, the int8 variants receive pre-quantized
(int8, scale) pairs (``ops.quantize.quantize_symmetric_host``) — the
per-batch kernels quantize/cast only the batch operand, never the
constant weights.

**Fused pipelines** (the Flare transplant, arxiv 1703.08219): a
multi-stage ``PipelineModel.transform`` pays one stage → dispatch →
complete cycle — one host round trip — PER STAGE. Models additionally
expose ``serving_stage(precision=...)`` returning a ``ServingStage``:
the stage's pure, UN-jitted device function plus its device-staged
constant weights. ``build_fused_pipeline_program`` composes the whole
chain inside ONE ``tracked_jit`` XLA program (scaler → PCA → classifier
as a single module — XLA fuses the elementwise stages straight into the
GEMMs), so a pipelined predict dispatches once per batch no matter how
many stages the pipeline holds. ``run_staged_pipeline`` is the
N-round-trip reference the parity suite holds the fused program
bit-equal to at f32/f64: each stage as its OWN jitted program with a
host sync between stages — same arithmetic, N dispatches instead of 1.
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np


class ServingStage(NamedTuple):
    """One model's composable contribution to a fused pipeline program.

    ``fn(x_dev, *weights) → y_dev`` is the PURE, un-jitted device
    function (jitting happens once, around the whole composed chain);
    ``weights`` are the device-staged constants for the requested
    precision. ``terminal`` marks output-typed stages (cluster labels,
    class probabilities) that can only sit LAST in a fused chain;
    ``fetch_dtype`` is the host dtype the stage's output carries when it
    IS last (matching the staged loop's output column exactly).
    """

    fn: Callable
    weights: Tuple
    algo: str
    terminal: bool = False
    fetch_dtype: Optional[np.dtype] = None


def resolve_serving_context(model=None,
                            device=None) -> Tuple[object, object, bool]:
    """``(device, dtype, donate)`` for a model's serving program: the
    model's resolved device and transform dtype, plus whether the
    donated kernel twin should be used (off-CPU only — on CPU donation
    is a no-op that warns). Tolerant of models without device params
    (host-stat scalers, ``PipelineModel`` itself): missing getters fall
    back to the default device and ``auto`` dtype.

    ``device`` (a concrete jax device from ``serve/placement.py`` — or
    a ``jax.sharding.Sharding`` for the sharded-program builder, which
    ``jax.device_put`` accepts in the device position) OVERRIDES the
    model's own device resolution: the multi-replica serving tier
    stages the same program onto every visible device."""
    from spark_rapids_ml_tpu.models.pca import (
        _resolve_device,
        _resolve_dtype,
    )

    get_dt = getattr(model, "getDtype", None)
    dtype = _resolve_dtype(get_dt() if callable(get_dt) else "auto")
    if device is None:
        get_dev = getattr(model, "getDeviceId", None)
        device = _resolve_device(get_dev() if callable(get_dev) else -1)
        donate = getattr(device, "platform", "cpu") != "cpu"
    else:
        donate = _donate_for(device)
    return device, dtype, donate


def _donate_for(device) -> bool:
    """Donation posture for an explicit device OR sharding target
    (donation is a warning no-op on CPU)."""
    platform = getattr(device, "platform", None)
    if platform is None:
        # a Sharding: every mesh device shares a platform
        devices = getattr(device, "device_set", None) or ()
        for dev in devices:
            platform = getattr(dev, "platform", "cpu")
            break
    return (platform or "cpu") != "cpu"


def resolve_pipeline_context(stages,
                             device=None) -> Tuple[object, object, bool]:
    """The shared ``(device, dtype, donate)`` a fused pipeline stages
    every weight under: the first stage carrying device params decides
    (a pipeline mixing device preferences is already incoherent for ONE
    XLA program); an all-host-stat chain falls back to the defaults.
    ``device`` overrides the resolution for the replica tier, exactly
    like ``resolve_serving_context``."""
    for stage in stages:
        if callable(getattr(stage, "getDeviceId", None)) and callable(
                getattr(stage, "getDtype", None)):
            return resolve_serving_context(stage, device=device)
    return resolve_serving_context(None, device=device)


def _prime_hook(kernel, weights: Tuple, device, dtype,
                ) -> Optional[Callable]:
    """The program's compile-without-execute hook: ``TrackedJit.prime``
    over an ABSTRACT batch spec (``jax.ShapeDtypeStruct`` carrying the
    staging sharding — signature-key-identical to a real staged batch,
    verified in the aotcache tests) plus the program's device-resident
    weight operands. Priming a bucket neither allocates nor transfers
    the batch: the warm-restart replay is pure executable loading. None
    for kernels without AOT priming (plain callables) — warmup then
    falls back to the execute path."""
    prime_fn = getattr(kernel, "prime", None)
    if not callable(prime_fn):
        return None

    def prime(n_rows: int, n_features: int) -> bool:
        import jax
        from jax.sharding import Sharding, SingleDeviceSharding

        sharding = (device if isinstance(device, Sharding)
                    else SingleDeviceSharding(device))
        spec = jax.ShapeDtypeStruct((int(n_rows), int(n_features)),
                                    dtype, sharding=sharding)
        return bool(prime_fn(spec, *weights))

    return prime


def staged_weight_bytes(weights, copies: int = 1) -> int:
    """Device bytes a program's staged constant weights occupy — the
    number the resource ledger (``obs.accounting``) charges per replica.
    Summed from each staged array's ``nbytes`` (jax and numpy arrays
    both carry it; weightless entries count 0), times ``copies`` for
    replicated sharding, where every mesh device holds a full physical
    copy."""
    total = 0
    for w in weights:
        try:
            total += int(getattr(w, "nbytes", 0) or 0)
        except (TypeError, ValueError):
            pass
    return total * max(int(copies), 1)


def build_serving_program(
    *,
    device,
    dtype,
    algo: str,
    precision: str,
    kernels: Dict[str, Callable],
    weights: Tuple,
    fetch_dtype: Optional[np.dtype] = None,
):
    """The shared put/run/fetch assembly.

    ``kernels`` maps precision → jitted kernel; ``weights`` is the tuple
    of device-staged constant operands the kernel takes after the batch
    (already cast/quantized for this precision); ``fetch_dtype`` is the
    host dtype the sync path's output carries (so pipeline outputs stay
    bit-equal to it — None keeps the device result's own dtype).
    Raises ``ValueError`` for an unknown precision.
    """
    import jax

    from spark_rapids_ml_tpu.obs.serving import ServingProgram

    kernel = kernels.get(precision)
    if kernel is None:
        raise ValueError(
            f"unknown serving precision {precision!r} "
            f"(one of {sorted(kernels)})"
        )

    def put(matrix):
        return jax.device_put(np.asarray(matrix, dtype=dtype), device)

    def run(x_dev):
        return kernel(x_dev, *weights)

    def fetch(out_dev):
        out = np.asarray(out_dev)
        if fetch_dtype is None:
            return out
        # astype(copy=False) converts when dtypes differ and is a no-op
        # when they already match
        return out.astype(fetch_dtype, copy=False)

    return ServingProgram(put=put, run=run, fetch=fetch,
                          dtype=np.dtype(dtype), algo=algo,
                          precision=precision,
                          prime=_prime_hook(kernel, weights, device, dtype),
                          weight_bytes=staged_weight_bytes(weights))


def build_host_stat_stage(model, fn, host_weights, algo: str,
                          device, dtype) -> ServingStage:
    """Shared ``serving_stage`` assembly for the host-stat scaler /
    feature-transformer families: the per-feature constants staged to
    the device once, the elementwise body left un-jitted for the
    fused-pipeline composer. Precision variants are meaningless for
    elementwise stages (the GEMM stages carry them), so every precision
    shares the native body. Float constants stage at the chain dtype;
    integer index arrays and boolean masks keep their own dtype."""
    import jax
    import jax.numpy as jnp

    if device is None or dtype is None:
        device, dtype, _ = resolve_serving_context(model)
    weights = tuple(
        jax.device_put(
            jnp.asarray(w, dtype=dtype if np.issubdtype(
                np.asarray(w).dtype, np.floating) else None),
            device)
        for w in host_weights
    )
    return ServingStage(fn=fn, weights=weights, algo=algo,
                        fetch_dtype=np.dtype(np.float64))


# -- whole-pipeline fusion ---------------------------------------------------


def collect_pipeline_stages(stages, precision: str, *, device, dtype,
                            ) -> Optional[List[ServingStage]]:
    """Every stage's ``ServingStage`` at ``precision`` under the shared
    device/dtype, or None when the chain is not fusable: a stage without
    the hook (host-path models, un-fusable families), a hook declining
    (returning None), or an output-typed (``terminal``) stage anywhere
    but last — labels cannot feed a downstream transformer."""
    specs: List[ServingStage] = []
    last = len(stages) - 1
    for i, stage in enumerate(stages):
        hook = getattr(stage, "serving_stage", None)
        if not callable(hook):
            return None
        spec = hook(precision=precision, device=device, dtype=dtype)
        if spec is None:
            return None
        if spec.terminal and i < last:
            return None
        specs.append(spec)
    return specs or None


def build_fused_pipeline_program(
    *,
    device,
    dtype,
    stages: List[ServingStage],
    precision: str,
    donate: bool,
    algo: str = "pipeline",
):
    """ONE ``tracked_jit`` XLA program for a whole fused stage chain.

    The composed function threads the batch through every stage body
    inside a single jit scope — the compiler sees the full dataflow and
    fuses elementwise stages into their neighboring GEMMs, and the
    serving loop pays ONE dispatch/complete cycle per batch instead of
    one per stage. Stage weights are passed flat (device-resident, zero
    transfer per call); the staged batch buffer is donated off-CPU
    exactly like the single-model serve kernels (a retry always
    re-stages from host rows).
    """
    import jax

    from spark_rapids_ml_tpu.obs.serving import ServingProgram
    from spark_rapids_ml_tpu.obs.xprof import tracked_jit

    fns = tuple(s.fn for s in stages)
    arities = tuple(len(s.weights) for s in stages)
    flat_weights = tuple(w for s in stages for w in s.weights)
    fetch_dtype = stages[-1].fetch_dtype

    def _fused(x, *flat):
        i = 0
        for fn, k in zip(fns, arities):
            x = fn(x, *flat[i:i + k])
            i += k
        return x

    label = "pipeline_fused_" + "_".join(s.algo for s in stages) \
            + f"_{precision}"
    kernel = tracked_jit(
        _fused, label=label,
        donate_argnums=(0,) if donate else (),
    )

    def put(matrix):
        return jax.device_put(np.asarray(matrix, dtype=dtype), device)

    def run(x_dev):
        return kernel(x_dev, *flat_weights)

    def fetch(out_dev):
        out = np.asarray(out_dev)
        if fetch_dtype is None:
            return out
        return out.astype(fetch_dtype, copy=False)

    return ServingProgram(put=put, run=run, fetch=fetch,
                          dtype=np.dtype(dtype), algo=algo,
                          precision=precision,
                          prime=_prime_hook(kernel, flat_weights, device,
                                            dtype),
                          weight_bytes=staged_weight_bytes(flat_weights))


# -- sharded big transforms ---------------------------------------------------


BATCH_AXIS = "batch"


def batch_mesh(devices):
    """A 1-D ``("batch",)`` mesh over the serving devices — the sharded
    big-transform layout (SNIPPETS.md [2]; arXiv:2112.09017: when the
    batch dimension is the sharded one, the GEMM-shaped transforms
    scale near-linearly)."""
    import numpy as _np

    from jax.sharding import Mesh

    return Mesh(_np.asarray(list(devices)), (BATCH_AXIS,))


def build_batch_sharded_program(
    model,
    *,
    devices,
    precision: str = "native",
):
    """A ``NamedSharding``-over-``("batch",)`` variant of a model's
    serving program: one HUGE request uses ALL chips instead of one.

    Rows are sharded across the mesh (``P("batch", None)``); the
    constant model weights are replicated (``P()``) — staged once at
    build, like every other serving program. The computation is built
    from the SAME un-jitted stage bodies the fused-pipeline composer
    uses (``serving_stage`` hooks, composed for pipelines exactly like
    ``build_fused_pipeline_program``), so the sharded program's
    arithmetic is the replicated program's arithmetic: every serving
    kernel here is row-independent, which keeps sharded outputs equal
    to single-device up to XLA's shape-dependent GEMM tiling (±ulp-
    scale FMA/reduction-order differences — the documented ε; often
    bit-equal in practice, tested in test_serve_multidevice.py).

    Returns ``None`` when the model cannot shard: fewer than 2 devices,
    no ``serving_stage`` hook (host-path families), a hook declining,
    or an un-fusable pipeline chain. ``precision`` follows the stage
    hooks (bf16/int8 compose exactly as in the fused path)."""
    devices = list(devices)
    if len(devices) < 2:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.obs.serving import ServingProgram
    from spark_rapids_ml_tpu.obs.xprof import tracked_jit

    mesh = batch_mesh(devices)
    replicated = NamedSharding(mesh, P())
    row_sharded = NamedSharding(mesh, P(BATCH_AXIS, None))

    stages = getattr(model, "stages", None)
    if isinstance(stages, (list, tuple)) and stages:
        # a fused pipeline: same chain-wiring contract as the fused
        # single-device program — an un-wired chain must not shard
        wired = getattr(model, "_chain_is_wired", None)
        if callable(wired) and not wired():
            return None
        _dev, dtype, _donate = resolve_pipeline_context(stages)
        specs = collect_pipeline_stages(stages, precision,
                                        device=replicated, dtype=dtype)
        if not specs:
            return None
        algo = "pipeline"
    else:
        hook = getattr(model, "serving_stage", None)
        if not callable(hook):
            return None
        _dev, dtype, _donate = resolve_serving_context(model)
        spec = hook(precision=precision, device=replicated, dtype=dtype)
        if spec is None:
            return None
        specs = [spec]
        algo = spec.algo

    fns = tuple(s.fn for s in specs)
    arities = tuple(len(s.weights) for s in specs)
    flat_weights = tuple(w for s in specs for w in s.weights)
    fetch_dtype = specs[-1].fetch_dtype

    def _chain(x, *flat):
        i = 0
        for fn, k in zip(fns, arities):
            x = fn(x, *flat[i:i + k])
            i += k
        return x

    label = (f"sharded_batch_{'_'.join(s.algo for s in specs)}"
             f"_{precision}_x{len(devices)}")
    kernel = tracked_jit(
        _chain, label=label,
        donate_argnums=(0,) if _donate_for(row_sharded) else (),
    )

    def put(matrix):
        # the host rows scatter straight into per-device shards — the
        # one host→device transfer a sharded request pays
        return jax.device_put(np.asarray(matrix, dtype=dtype),
                              row_sharded)

    def run(x_dev):
        return kernel(x_dev, *flat_weights)

    def fetch(out_dev):
        out = np.asarray(out_dev)  # gathers the shards
        if fetch_dtype is None:
            return out
        return out.astype(fetch_dtype, copy=False)

    return ServingProgram(put=put, run=run, fetch=fetch,
                          dtype=np.dtype(dtype), algo=algo,
                          precision=precision,
                          # the batch operand's sharding IS the prime
                          # spec's placement (the hook accepts a
                          # Sharding in the device slot)
                          prime=_prime_hook(kernel, flat_weights,
                                            row_sharded, dtype),
                          # replicated weights: every mesh device holds
                          # a full physical copy
                          weight_bytes=staged_weight_bytes(
                              flat_weights, copies=len(devices)))


def run_staged_pipeline(model, x, precision: str = "native") -> np.ndarray:
    """The N-round-trip reference: each composable stage as its OWN
    jitted program with a host sync between stages — the per-stage
    dispatch/complete loop the fused program replaces, built from the
    SAME stage bodies so the parity suite can hold fused bit-equal to
    staged at f32/f64. Raises ``ValueError`` when the pipeline is not
    fusable (mirrors the hook declining)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.obs.xprof import tracked_jit

    stages = getattr(model, "stages", None) or []
    device, dtype, _donate = resolve_pipeline_context(stages)
    specs = collect_pipeline_stages(stages, precision,
                                    device=device, dtype=dtype)
    if not specs:
        raise ValueError("pipeline has no fusable stage chain")
    out = np.asarray(x)
    for i, spec in enumerate(specs):
        kernel = tracked_jit(
            spec.fn, label=f"pipeline_staged_{spec.algo}_{i}_{precision}")
        x_dev = jax.device_put(jnp.asarray(out, dtype=out.dtype
                                           if i else dtype), device)
        # the host sync between stages IS the point of comparison
        out = np.asarray(kernel(x_dev, *spec.weights))
    if specs[-1].fetch_dtype is not None:
        out = out.astype(specs[-1].fetch_dtype, copy=False)
    return out
