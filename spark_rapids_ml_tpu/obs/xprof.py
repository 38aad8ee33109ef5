"""Compile/recompile/cost telemetry for jitted entry points.

``tracked_jit`` is a drop-in replacement for ``jax.jit`` (same kwargs) that
makes XLA compilation a first-class observable instead of an invisible tax:

* **lowering + compile wall-clock** per distinct signature, measured by
  driving the AOT path explicitly (``fn.lower(...).compile()``) so the
  numbers are the real jaxpr-trace/MLIR-lower and backend-compile costs,
  not first-call-minus-steady-state guesswork;
* **a recompile counter** keyed by the abstract signature — pytree
  structure, (shape, dtype, weak-type, sharding) of every array leaf, and
  the static argument values — with a loud "recompile storm" warning when
  one function accumulates more distinct signatures than
  ``SPARK_RAPIDS_ML_TPU_RECOMPILE_STORM`` (default 8): the classic symptom
  of un-padded batch tails or a static arg that should be dynamic;
* **HLO ``cost_analysis`` FLOPs / bytes-accessed and compiled memory
  sizes** per signature (``CompileEvent``), handed on every executed call
  to the serving report and the fit-path monitor (``obs.fitmon``).

What ``tracked_jit`` cannot see — programs JAX builds for eager ops —
reaches the active fit through one
``jax.monitoring`` listener (``_on_executable_built``) as
``FitReport.programs_compiled`` / ``programs_fetched``.

Execution goes through the cached compiled executable, so tracking adds no
extra compiles: signature miss → one lower+compile (exactly what ``jax.jit``
would have paid) + cost analysis; signature hit → call the cached
executable. Tracer inputs (the wrapped function invoked inside another
traced computation) bypass tracking entirely and defer to the plain jitted
function. Any AOT-path surprise falls back to the plain jitted call for
that signature — telemetry must never break a kernel.
"""

from __future__ import annotations

import inspect
import os
import threading
import time
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

STORM_ENV = "SPARK_RAPIDS_ML_TPU_RECOMPILE_STORM"
_DEFAULT_STORM_THRESHOLD = 8


def storm_threshold() -> int:
    try:
        return int(os.environ.get(STORM_ENV, _DEFAULT_STORM_THRESHOLD))
    except ValueError:
        return _DEFAULT_STORM_THRESHOLD


@dataclass
class CompileEvent:
    """One observed compilation of one tracked function signature."""

    label: str
    key: Tuple
    lowering_seconds: float
    compile_seconds: float
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    memory: Dict[str, int] = field(default_factory=dict)
    recompile: bool = False
    fallback: bool = False

    def as_dict(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "lowering_seconds": self.lowering_seconds,
            "compile_seconds": self.compile_seconds,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "memory": dict(self.memory),
            "recompile": self.recompile,
            "fallback": self.fallback,
        }


class _CacheEntry:
    __slots__ = ("compiled", "flops", "bytes_accessed", "memory", "fallback")

    def __init__(self, compiled=None, flops=None, bytes_accessed=None,
                 memory=None, fallback=False):
        self.compiled = compiled
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.memory = memory or {}
        self.fallback = fallback


# Global compile log (bounded) + per-label aggregate, for tests, dumps and
# `compile_stats()`.
_log_lock = threading.Lock()
_compile_log: list = []
_COMPILE_LOG_CAP = 512


def _log_event(event: CompileEvent) -> None:
    with _log_lock:
        _compile_log.append(event)
        if len(_compile_log) > _COMPILE_LOG_CAP:
            del _compile_log[: len(_compile_log) - _COMPILE_LOG_CAP]


def compile_log():
    """The recent ``CompileEvent`` history (newest last)."""
    with _log_lock:
        return list(_compile_log)


def compile_stats() -> Dict[str, Dict[str, Any]]:
    """Aggregate per-label compile accounting across all tracked functions:
    ``{label: {compiles, recompiles, compile_seconds, flops, signatures}}``
    (``signatures`` counts DISTINCT signatures seen in the log window)."""
    out: Dict[str, Dict[str, Any]] = {}
    seen_keys: Dict[str, set] = {}
    for ev in compile_log():
        agg = out.setdefault(ev.label, {
            "compiles": 0, "recompiles": 0, "compile_seconds": 0.0,
            "flops": 0.0, "signatures": 0,
        })
        agg["compiles"] += 1
        agg["recompiles"] += int(ev.recompile)
        agg["compile_seconds"] += ev.lowering_seconds + ev.compile_seconds
        if ev.flops:
            agg["flops"] += ev.flops
        keys = seen_keys.setdefault(ev.label, set())
        try:
            keys.add(ev.key)
        except TypeError:
            keys.add(repr(ev.key))
        agg["signatures"] = len(keys)
    return out


def signature_count(label_prefix: str) -> int:
    """Distinct compiled signatures across tracked functions whose label
    starts with ``label_prefix`` — the warmup-ladder assertion helper for
    the serving tier, where the precision × bucket ladder registers one
    label per variant (``pca_transform_serve``, ``pca_transform_bf16``,
    ...) and one signature per bucket under each."""
    return sum(
        stats["signatures"]
        for label, stats in compile_stats().items()
        if label.startswith(label_prefix)
    )


def reset_compile_log() -> None:
    with _log_lock:
        _compile_log.clear()


# Live TrackedJit instances (weak: module-level kernels pin themselves
# through their module; runtime-built programs must stay collectable).
# clear_all_signature_caches() is the warm-restart rehearsal switch: it
# makes every tracked function forget its in-memory executables, so the
# next call exercises the persistent disk cache exactly like a freshly
# restarted process would.
_instances: "weakref.WeakSet[TrackedJit]" = weakref.WeakSet()


def clear_all_signature_caches() -> None:
    """Drop every live tracked function's in-memory signature cache
    (the persistent disk cache, if configured, is untouched). Used by
    the warm-restart integration test and the cold-start bench to
    simulate a process restart in-process."""
    for inst in list(_instances):
        inst.clear_cache()


def forget_fallback_signatures() -> None:
    """Drop, from every live tracked function, the signatures that gave up
    on their AOT executable: ``fallback_signatures()`` then counts only
    what falls back from here on (a dropped signature tries its AOT
    compile again at its next call). For a reader of that process-wide
    count that shares its process, as a test shares a worker."""
    for inst in list(_instances):
        inst.forget_fallbacks()


def fallback_signatures() -> Dict[str, int]:
    """``{label: count}`` of signatures, across live tracked functions,
    that gave up on their AOT executable (``lower().compile()`` raised, or
    the compiled call rejected its arguments) and run the plain jitted
    function instead. Such a kernel still answers, but its compile is
    invisible to the telemetry — the chip smoke asserts this is empty."""
    out: Dict[str, int] = {}
    for inst in list(_instances):
        count = inst.stats()["fallbacks"]
        if count:
            out[inst.label] = out.get(inst.label, 0) + count
    return out


def _leaf_sig(x) -> Tuple:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        # Shardings are hashable with value equality — used directly in the
        # key (repr() would stringify the whole mesh on every hot call).
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            try:
                hash(sharding)
            except TypeError:
                sharding = repr(sharding)
        return (
            "arr",
            tuple(int(s) for s in shape),
            str(dtype),
            bool(getattr(x, "weak_type", False)),
            sharding,
        )
    if isinstance(x, (bool, int, float, complex)):
        # value-independent: jit traces python scalars as (weak) 0-d arrays,
        # so a changed value is NOT a recompile
        return ("py", type(x).__name__)
    if x is None:
        return ("none",)
    return ("obj", type(x).__name__)


def _hashable(value) -> Any:
    try:
        hash(value)
        return value
    except TypeError:
        return repr(value)


def _cost_fields(compiled) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from ``Compiled.cost_analysis()`` — which
    returns a list-of-dicts on some backends, a dict on others, and may
    report -1 for unknowns."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return None, None

    def _pick(name):
        v = cost.get(name)
        if v is None or v < 0:
            return None
        return float(v)

    return _pick("flops"), _pick("bytes accessed")


def _memory_fields(compiled) -> Dict[str, int]:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for name in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, name, None)
        if v is not None:
            out[name] = int(v)
    return out


class TrackedJit:
    """The ``tracked_jit`` wrapper object. Callable like the jitted fn;
    exposes ``stats()`` for introspection."""

    def __init__(self, fn, *, label: Optional[str] = None,
                 storm_threshold: Optional[int] = None, **jit_kwargs):
        import jax

        self._fn = fn
        self.label = label or getattr(fn, "__qualname__", None) or getattr(
            fn, "__name__", "jit_fn"
        )
        self._jitted = jax.jit(fn, **jit_kwargs)
        # Signature-less callables (shard_map wrappers, *args shims) run in
        # "generic" mode: no canonicalization, statics located by name only.
        try:
            self._signature = inspect.signature(fn)
            self._params = list(self._signature.parameters.values())
            if any(p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
                   for p in self._params):
                self._signature = None
                self._params = []
        except (ValueError, TypeError):
            self._signature = None
            self._params = []
        self._storm_threshold = storm_threshold
        self._storm_warned = False
        self._lock = threading.Lock()
        # Serializes first-compile per instance so concurrent first calls
        # with one signature cannot double-compile / double-count.
        self._compile_lock = threading.Lock()
        self._cache: Dict[Any, _CacheEntry] = {}

        static_names = set()
        names = jit_kwargs.get("static_argnames") or ()
        if isinstance(names, str):
            names = (names,)
        static_names.update(names)
        for i in jit_kwargs.get("static_argnums") or ():
            if 0 <= i < len(self._params):
                static_names.add(self._params[i].name)
        self._static_names = frozenset(static_names)
        self._static_positions = frozenset(
            i for i, p in enumerate(self._params)
            if p.name in self._static_names
        )
        # functools.wraps surface so @tracked_jit looks like the function
        for attr in ("__name__", "__qualname__", "__doc__", "__module__"):
            try:
                setattr(self, attr, getattr(fn, attr))
            except (AttributeError, TypeError):
                pass
        self.__wrapped__ = fn
        _instances.add(self)

    # -- introspection -----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "label": self.label,
                "signatures": len(self._cache),
                "fallbacks": sum(1 for e in self._cache.values()
                                 if e.fallback),
            }

    def clear_cache(self) -> None:
        with self._lock:
            self._cache.clear()
            self._storm_warned = False

    def forget_fallbacks(self) -> None:
        with self._lock:
            for key in [k for k, e in self._cache.items() if e.fallback]:
                del self._cache[key]

    # AOT passthroughs so call sites that reach for the raw jit still work.
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def prime(self, *args, **kwargs) -> bool:
        """Ensure the signature for these (abstract) arguments is
        compiled — via the persistent executable cache when configured,
        else a fresh AOT compile — WITHOUT executing the program.

        The warm-restart replay path: executing a zero batch per bucket
        just to reach the compiler wastes restart time (and on a real
        chip, device time); priming loads/compiles the executable and
        returns. Returns False when the signature had to fall back to
        the plain jitted path (it will compile lazily on first call)."""
        import jax

        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves((args, kwargs))):
            return False
        try:
            cargs, ckwargs = self._canonicalize(args, kwargs)
            key = self._signature_key(cargs, ckwargs)
        except Exception:
            return False
        with self._lock:
            entry = self._cache.get(key)
        if entry is None:
            with self._compile_lock:
                with self._lock:
                    entry = self._cache.get(key)
                if entry is None:
                    entry = self._compile_entry(key, cargs, ckwargs)
                    with self._lock:
                        self._cache[key] = entry
                        n_signatures = len(self._cache)
                    self._maybe_warn_storm(n_signatures)
        return not entry.fallback and entry.compiled is not None

    # -- the call path -----------------------------------------------------

    def _canonicalize(self, args, kwargs):
        # Normalize positional-vs-keyword passing of the same parameter so
        # both spellings share one signature key. Defaults are NOT applied:
        # jit never sees unpassed parameters (their defaults resolve inside
        # the traced function — they may be non-array values like solver
        # strings), so neither may we.
        if self._signature is None:
            return args, dict(kwargs)
        bound = self._signature.bind(*args, **kwargs)
        return bound.args, bound.kwargs

    def _split_dynamic(self, cargs, ckwargs):
        dyn_args = tuple(a for i, a in enumerate(cargs)
                         if i not in self._static_positions)
        dyn_kwargs = {k: v for k, v in ckwargs.items()
                      if k not in self._static_names}
        return dyn_args, dyn_kwargs

    def _signature_key(self, cargs, ckwargs):
        import jax

        dyn_args, dyn_kwargs = self._split_dynamic(cargs, ckwargs)
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        statics = tuple(
            (self._params[i].name, _hashable(cargs[i]))
            for i in sorted(self._static_positions) if i < len(cargs)
        ) + tuple(
            (k, _hashable(v)) for k, v in sorted(ckwargs.items())
            if k in self._static_names
        )
        return (treedef, tuple(_leaf_sig(x) for x in leaves), statics)

    def _maybe_warn_storm(self, n_signatures: int) -> None:
        threshold = (self._storm_threshold if self._storm_threshold
                     is not None else storm_threshold())
        if n_signatures >= threshold and not self._storm_warned:
            self._storm_warned = True
            warnings.warn(
                f"recompile storm: {self.label} has compiled "
                f"{n_signatures} distinct signatures (threshold "
                f"{threshold}). Usual causes: un-padded batch tails "
                f"(pad + mask to a fixed shape) or a static argument that "
                f"changes per call. Set {STORM_ENV} to tune.",
                RuntimeWarning,
                stacklevel=3,
            )
            try:
                from spark_rapids_ml_tpu.obs.metrics import get_registry

                get_registry().counter(
                    "sparkml_recompile_storms_total",
                    "tracked functions crossing the recompile-storm "
                    "threshold", ("fn",),
                ).inc(fn=self.label)
            except Exception:
                pass

    def _record_compile(self, event: CompileEvent) -> None:
        _log_event(event)
        try:
            from spark_rapids_ml_tpu.obs.metrics import get_registry
            from spark_rapids_ml_tpu.obs.report import current_fit

            reg = get_registry()
            reg.counter(
                "sparkml_xla_compiles_total",
                "XLA compilations of tracked jitted functions", ("fn",),
            ).inc(fn=self.label)
            if event.recompile:
                reg.counter(
                    "sparkml_xla_recompiles_total",
                    "re-compilations (new signature after the first)",
                    ("fn",),
                ).inc(fn=self.label)
            reg.histogram(
                "sparkml_xla_compile_seconds",
                "lowering+backend-compile wall-clock", ("fn",),
            ).observe(event.lowering_seconds + event.compile_seconds,
                      fn=self.label)
            current_fit().record_compile(
                self.label,
                event.lowering_seconds + event.compile_seconds,
                recompile=event.recompile,
            )
            from spark_rapids_ml_tpu.obs.serving import current_transform

            current_transform().record_compile(
                self.label,
                event.lowering_seconds + event.compile_seconds,
                recompile=event.recompile,
            )
        except Exception:
            pass  # telemetry must never break a kernel

    def _record_execution(self, entry: _CacheEntry) -> None:
        try:
            from spark_rapids_ml_tpu.obs.serving import current_transform

            current_transform().record_program(
                self.label, entry.flops, entry.bytes_accessed
            )
            from spark_rapids_ml_tpu.obs import fitmon

            fitmon.record_program(
                self.label, entry.flops, entry.bytes_accessed
            )
        except Exception:
            pass

    def _persistent_cache(self):
        """The process's persistent executable cache, or None. Resolved
        per compile (not per call — the miss path already pays a full
        XLA compile, the hit path one small file read): a cache the
        operator enables mid-process must start serving hits."""
        try:
            from spark_rapids_ml_tpu.obs.aotcache import (
                get_executable_cache,
            )

            return get_executable_cache()
        except Exception:
            return None  # cache plumbing must never break a kernel

    def _compile_entry(self, key, cargs, ckwargs) -> _CacheEntry:
        recompile = bool(self._cache)
        # The persistent executable cache (obs/aotcache.py): a disk hit
        # skips lower+compile entirely — no CompileEvent is recorded, so
        # signature_count() stays at 0 across a warm restart (the
        # zero-fresh-compiles assertion the cold-start bench makes).
        cache = self._persistent_cache()
        if cache is not None:
            loaded = cache.load(self.label, key)
            if loaded is not None and loaded.compiled is not None:
                return _CacheEntry(
                    compiled=loaded.compiled, flops=loaded.flops,
                    bytes_accessed=loaded.bytes_accessed,
                    memory=loaded.memory,
                )
        t0 = time.perf_counter()
        try:
            lowered = self._jitted.lower(*cargs, **ckwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception:
            # AOT path unavailable for this signature (exotic pytree,
            # backend quirk): fall back to the plain jitted call forever
            # for this key, timing its first call as the compile cost.
            t1 = time.perf_counter()
            entry = _CacheEntry(fallback=True)
            self._record_compile(CompileEvent(
                label=self.label, key=key,
                lowering_seconds=t1 - t0, compile_seconds=0.0,
                recompile=recompile, fallback=True,
            ))
            return entry
        flops, nbytes = _cost_fields(compiled)
        memory = _memory_fields(compiled)
        entry = _CacheEntry(compiled=compiled, flops=flops,
                            bytes_accessed=nbytes, memory=memory)
        self._record_compile(CompileEvent(
            label=self.label, key=key,
            lowering_seconds=t1 - t0, compile_seconds=t2 - t1,
            flops=flops, bytes_accessed=nbytes, memory=memory,
            recompile=recompile,
        ))
        if cache is not None:
            # store failures are counted inside the cache and ignored:
            # the in-memory entry above is already good
            cache.store(self.label, key, compiled, flops=flops,
                        bytes_accessed=nbytes, memory=memory,
                        compile_seconds=(t1 - t0) + (t2 - t1))
        return entry

    def __call__(self, *args, **kwargs):
        import jax

        # Inside another trace (vmap/jit/scan): stay out of the way.
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves((args, kwargs))):
            return self._jitted(*args, **kwargs)
        try:
            cargs, ckwargs = self._canonicalize(args, kwargs)
            key = self._signature_key(cargs, ckwargs)
        except Exception:
            return self._jitted(*args, **kwargs)

        with self._lock:
            entry = self._cache.get(key)
        if entry is None:
            with self._compile_lock:
                with self._lock:
                    entry = self._cache.get(key)
                if entry is None:
                    entry = self._compile_entry(key, cargs, ckwargs)
                    with self._lock:
                        self._cache[key] = entry
                        n_signatures = len(self._cache)
                    self._maybe_warn_storm(n_signatures)

        self._record_execution(entry)
        if entry.fallback or entry.compiled is None:
            return self._jitted(*cargs, **ckwargs)
        dyn_args, dyn_kwargs = self._split_dynamic(cargs, ckwargs)
        try:
            return entry.compiled(*dyn_args, **dyn_kwargs)
        except Exception:
            # Executable/argument mismatch we failed to predict (e.g. a
            # sharding nuance outside the signature key): permanently fall
            # back to the plain jitted path for this signature.
            with self._lock:
                entry.fallback = True
            return self._jitted(*cargs, **ckwargs)


def tracked_jit(fn=None, *, label: Optional[str] = None,
                storm_threshold: Optional[int] = None, **jit_kwargs):
    """``jax.jit`` with compile/recompile/cost telemetry (see module doc).

    Usable bare (``@tracked_jit``), with jit kwargs
    (``@tracked_jit(static_argnames=("k",), donate_argnums=(0,))``), or via
    ``partial`` exactly like ``jax.jit``.
    """
    if fn is None:
        return lambda f: TrackedJit(f, label=label,
                                    storm_threshold=storm_threshold,
                                    **jit_kwargs)
    return TrackedJit(fn, label=label, storm_threshold=storm_threshold,
                      **jit_kwargs)


def track_compiles(fn, **jit_kwargs) -> TrackedJit:
    """Imperative form of ``tracked_jit`` for call sites that build their
    jitted function at runtime (``track_compiles(f, static_argnames=...)``)."""
    if isinstance(fn, TrackedJit):
        return fn
    return TrackedJit(fn, **jit_kwargs)


def peak_flops_per_second() -> Optional[float]:
    """This process's per-chip peak dense FLOP/s (bf16), or None when the
    device kind has no published number (CPU included) — the denominator
    for every analytic-MFU figure."""
    try:
        import jax

        from spark_rapids_ml_tpu.utils.platform import PEAK_FLOPS_BF16

        device = jax.devices()[0]
        if device.platform == "cpu":
            return None
        return PEAK_FLOPS_BF16.get(str(device.device_kind))
    except Exception:
        return None


def analytic_mfu(flops: Optional[float],
                 seconds: Optional[float]) -> Optional[float]:
    """Analytic MFU: HLO cost-analysis FLOPs over wall-clock over the
    chip's peak. None when any input (or the peak) is unknown."""
    if not flops or not seconds or seconds <= 0:
        return None
    peak = peak_flops_per_second()
    if not peak:
        return None
    return flops / seconds / peak


# -- every executable JAX builds, tracked or not ----------------------------

_BUILT_EVENT = "/jax/core/compile/backend_compile_duration"
_FETCHED_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_executable_built(event: str, duration: float, **_) -> None:
    """``jax.monitoring`` duration listener. JAX reports ``_BUILT_EVENT``
    once per executable it builds, compiled or read back from the persistent
    cache, and ``_FETCHED_EVENT`` just before it for one that was read back;
    both fire on the thread that asked, so the fit's context is the caller's."""
    if event not in (_BUILT_EVENT, _FETCHED_EVENT):
        return
    try:
        from spark_rapids_ml_tpu.obs.report import current_fit

        current_fit().record_executable(fetched=event == _FETCHED_EVENT)
    except Exception:
        pass  # telemetry must never break a compile


import jax.monitoring  # noqa: E402 - the package has imported jax by now

# once: a module is imported once
jax.monitoring.register_event_duration_secs_listener(_on_executable_built)
