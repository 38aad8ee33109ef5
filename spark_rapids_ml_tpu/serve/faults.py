"""Injectable fault plane: make the serving tier fail on purpose.

A device backend hang that stalls the transform path for hours cannot be
rehearsed unless the serving stack can be made to misbehave on demand —
without that, the breaker/retry/fallback machinery this package adds
would ship untested against the very failures it exists to absorb. This
module is the chaos-engineering control plane for ``serve/``:

* **programmatic API** — ``fault_plane().inject(model="pca", kind="raise",
  count=5)`` arms a fault; ``clear()`` disarms everything. Tests drive
  the whole matrix in-process.
* **env API** — ``SPARK_RAPIDS_ML_TPU_SERVE_FAULTS`` arms faults at
  process start (chaos drills against a real deployment):
  comma-separated ``model:kind[:count[:start[:seconds]]]`` specs, e.g.
  ``"pca_embedder:raise:5"`` (first five calls fail) or
  ``"*:latency:*:0:0.05"`` (every call on every model +50 ms).
* **deterministic targeting** — each spec matches a model name (or
  ``*``), fires from call index ``start``, at most ``count`` times
  (``*``/``inf`` = forever), on every ``every``-th call. Call indices
  are counted per model per site, so a chaos test that says "fail calls
  3..5 on model A" reproduces exactly, run after run. At most ONE fault
  fires per call: the first-armed matching spec wins (a call that
  raises cannot also be slow), and later/wildcard specs apply on the
  calls more specific ones leave alone.

Fault kinds (the failure modes the r04/r05 logs actually contain):

* ``raise``   — the device backend errors: ``InjectedBackendError``
  (classified as a backend fault by the engine → breaker food);
* ``stall``   — the call wedges for ``seconds`` (default 30 — long
  enough to trip any sane worker watchdog budget);
* ``nan``     — the transform "succeeds" but its output is corrupted
  with NaNs (the silent-poison failure the numerics sentinel exists
  for);
* ``latency`` — the call completes but ``seconds`` (default 0.05)
  slower: SLO latency-burn food;
* ``crash_worker`` — the batcher's worker thread dies
  (``InjectedWorkerCrash``, a ``BaseException`` so nothing on the batch
  path accidentally swallows it) — exercises worker supervision.

Injection sites: the engine consults ``begin_call(model)`` around every
coalesced transform (raise/stall/nan/latency), the batcher consults
``worker_fault(model)`` in its worker loop (crash_worker). Every fired
fault counts in ``sparkml_serve_faults_injected_total{model,kind}`` so a
chaos run's injected-vs-observed arithmetic is checkable from the
metrics snapshot alone.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

from spark_rapids_ml_tpu.obs import get_registry

FAULTS_ENV = "SPARK_RAPIDS_ML_TPU_SERVE_FAULTS"

KINDS = ("raise", "stall", "nan", "latency", "crash_worker")

# Transform-site kinds vs worker-loop kinds: one call index per site so
# "fail call 3" means the 3rd *transform*, not the 3rd loop iteration.
_TRANSFORM_KINDS = frozenset({"raise", "stall", "nan", "latency"})

_DEFAULT_SECONDS = {"stall": 30.0, "latency": 0.05}


class InjectedBackendError(RuntimeError):
    """An injected device-backend failure — the engine classifies it
    exactly like an ``XlaRuntimeError``/``Unavailable`` from a real
    backend fault (retryable, breaker-counted)."""


class InjectedWorkerCrash(BaseException):
    """Kills a batcher worker thread. Deliberately a ``BaseException``:
    the batch-execution path catches ``Exception`` to survive batch
    failures, and a worker *crash* must not be absorbed by it."""


class FaultSpec:
    """One armed fault: targeting + what to do when it fires.

    ``device`` (None = any) narrows the fault to ONE replica's device —
    the replica-drain chaos drill faults a single chip's dispatches and
    proves the placement tier sheds onto the siblings. ``version``
    (None = any) narrows it to ONE registry version's call sites — the
    canary-rollback drill faults only the CANDIDATE version's
    dispatches and proves the rollout tier rolls the alias back while
    the incumbent keeps serving. A device- or version-targeted spec
    never fires at call sites that carry no matching identity (the
    worker loop is version-less; the blocking sync path is
    device-less)."""

    __slots__ = ("model", "kind", "count", "start", "every", "seconds",
                 "device", "version", "fired")

    def __init__(self, model: str = "*", kind: str = "raise", *,
                 count: Optional[int] = 1, start: int = 0, every: int = 1,
                 seconds: Optional[float] = None,
                 device: Optional[str] = None,
                 version: Optional[int] = None):
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r} (one of {KINDS})")
        if every < 1:
            raise ValueError("every must be >= 1")
        self.model = model
        self.kind = kind
        self.count = None if count is None else int(count)
        self.start = int(start)
        self.every = int(every)
        self.seconds = (float(seconds) if seconds is not None
                        else _DEFAULT_SECONDS.get(kind, 0.0))
        self.device = device
        self.version = None if version is None else int(version)
        self.fired = 0

    def matches(self, model: str, index: int,
                device: Optional[str] = None,
                version: Optional[int] = None) -> bool:
        if self.model not in ("*", model):
            return False
        if self.device is not None and device != self.device:
            return False
        if self.version is not None and version != self.version:
            return False
        if index < self.start or (index - self.start) % self.every != 0:
            return False
        return self.count is None or self.fired < self.count

    def as_dict(self) -> Dict[str, Any]:
        return {
            "model": self.model,
            "kind": self.kind,
            "count": self.count,
            "start": self.start,
            "every": self.every,
            "seconds": self.seconds,
            "device": self.device,
            "version": self.version,
            "fired": self.fired,
        }


def parse_fault_specs(raw: str) -> List[FaultSpec]:
    """``model:kind[:count[:start[:seconds]]]`` specs, comma-separated.

    ``count`` of ``*``/``inf`` means forever. Malformed specs raise
    ``ValueError`` — a chaos drill with a typo'd fault must fail loudly,
    not run a different experiment than the operator asked for.
    """
    specs: List[FaultSpec] = []
    for chunk in raw.split(","):
        chunk = chunk.strip()
        if not chunk:
            continue
        parts = chunk.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"bad fault spec {chunk!r} (want model:kind[:count"
                "[:start[:seconds]]])"
            )
        model, kind = parts[0], parts[1]
        count: Optional[int] = 1
        if len(parts) > 2:
            count = (None if parts[2] in ("*", "inf", "")
                     else int(parts[2]))
        start = int(parts[3]) if len(parts) > 3 and parts[3] else 0
        seconds = float(parts[4]) if len(parts) > 4 and parts[4] else None
        specs.append(FaultSpec(model, kind, count=count, start=start,
                               seconds=seconds))
    return specs


class FaultPlane:
    """The process-wide registry of armed faults.

    Thread-safe: the engine/batcher consult it on every call; chaos
    tests arm/disarm from other threads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._specs: List[FaultSpec] = []
        self._calls: Dict[str, int] = {}          # transform-site index
        self._worker_calls: Dict[str, int] = {}   # worker-loop index
        self._m_injected = get_registry().counter(
            "sparkml_serve_faults_injected_total",
            "faults fired by the injection plane", ("model", "kind"),
        )

    # -- arming ------------------------------------------------------------

    def inject(self, model: str = "*", kind: str = "raise", *,
               count: Optional[int] = 1, start: int = 0, every: int = 1,
               seconds: Optional[float] = None,
               device: Optional[str] = None,
               version: Optional[int] = None) -> FaultSpec:
        """Arm one fault; returns the live spec (its ``fired`` counter
        updates as the fault fires). ``device`` narrows it to one
        replica's dispatch site (the replica-drain drill); ``version``
        narrows it to one registry version's call sites (the
        canary-rollback drill — a candidate-targeted fault never fires
        on the incumbent)."""
        spec = FaultSpec(model, kind, count=count, start=start,
                         every=every, seconds=seconds, device=device,
                         version=version)
        with self._lock:
            self._specs.append(spec)
        return spec

    def load_env(self, raw: Optional[str] = None) -> int:
        """Arm faults from ``SPARK_RAPIDS_ML_TPU_SERVE_FAULTS`` (or an
        explicit spec string); returns how many were armed."""
        raw = os.environ.get(FAULTS_ENV, "") if raw is None else raw
        specs = parse_fault_specs(raw)
        with self._lock:
            self._specs.extend(specs)
        return len(specs)

    def clear(self) -> None:
        """Disarm every fault and reset the deterministic call counters
        (the next experiment starts from call index 0)."""
        with self._lock:
            self._specs = []
            self._calls.clear()
            self._worker_calls.clear()

    def active(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [s.as_dict() for s in self._specs]

    # -- firing ------------------------------------------------------------

    def _next(self, counters: Dict[str, int], model: str,
              kinds, device: Optional[str] = None,
              version: Optional[int] = None) -> Optional[FaultSpec]:
        with self._lock:
            index = counters.get(model, 0)
            counters[model] = index + 1
            for spec in self._specs:
                if spec.kind in kinds and spec.matches(model, index,
                                                      device, version):
                    spec.fired += 1
                    break
            else:
                return None
        self._m_injected.inc(model=model, kind=spec.kind)
        return spec

    def begin_call(self, model: str,
                   device: Optional[str] = None,
                   version: Optional[int] = None) -> Optional[FaultSpec]:
        """Advance ``model``'s transform-site call index and return the
        fault (if any) that fires on this call. The caller applies it:
        ``apply_pre`` before the model call, ``corrupt`` on the output
        for ``nan``. ``device`` is the dispatching replica's device
        label and ``version`` the serving registry version (None at
        sites without that identity) — targeted specs only fire when
        theirs matches."""
        return self._next(self._calls, model, _TRANSFORM_KINDS,
                          device=device, version=version)

    def worker_fault(self, model: str) -> Optional[FaultSpec]:
        """The worker-loop site: a matched ``crash_worker`` spec (the
        batcher raises ``InjectedWorkerCrash`` for it)."""
        return self._next(self._worker_calls, model, ("crash_worker",))


def apply_pre(spec: FaultSpec) -> None:
    """Apply a fired fault's before-the-model-call effect."""
    if spec.kind == "raise":
        raise InjectedBackendError(
            f"injected backend fault on {spec.model!r} "
            f"(fired {spec.fired}/{spec.count or 'inf'})"
        )
    if spec.kind in ("stall", "latency"):
        time.sleep(spec.seconds)


def corrupt(spec: FaultSpec, out):
    """Apply a fired ``nan`` fault to a transform output: the first row
    becomes NaN (float outputs) — the silent-poison corruption the
    NaN guard / numerics sentinel must catch."""
    import numpy as np

    if spec.kind != "nan":
        return out
    out = np.array(out, dtype=np.float64, copy=True)
    if out.size:
        out.reshape(out.shape[0], -1)[0, :] = np.nan
    return out


_plane: Optional[FaultPlane] = None
_plane_lock = threading.Lock()


def fault_plane() -> FaultPlane:
    """The process singleton; arms ``SPARK_RAPIDS_ML_TPU_SERVE_FAULTS``
    on first access when set."""
    global _plane
    with _plane_lock:
        if _plane is None:
            _plane = FaultPlane()
            if os.environ.get(FAULTS_ENV):
                _plane.load_env()
        return _plane


def reset_fault_plane() -> None:
    """Drop the singleton (tests: a fresh plane with fresh counters)."""
    global _plane
    with _plane_lock:
        _plane = None


__all__ = [
    "FAULTS_ENV",
    "FaultPlane",
    "FaultSpec",
    "InjectedBackendError",
    "InjectedWorkerCrash",
    "KINDS",
    "apply_pre",
    "corrupt",
    "fault_plane",
    "parse_fault_specs",
    "reset_fault_plane",
]
