"""Thread-safe metrics registry: counters, gauges, histograms with labels.

The reference plugin has NO metrics at all — its only signals are Spark
``Logging`` lines and NVTX ranges (SURVEY.md §3.4–3.5, §5). This registry is
the missing accounting layer the tuning papers lean on (Alchemist's
per-collective cost model, arxiv 1805.11800; the TPU distributed linear
algebra accounting in arxiv 2112.09017): every fit increments a small set of
well-known series (``sparkml_fits_total``, ``sparkml_fit_seconds``,
``sparkml_collective_bytes_total``, …) that can be scraped as Prometheus
text or embedded as a JSON snapshot in bench records.

Design constraints:

* stdlib only (no ``prometheus_client`` dependency — the container may not
  have it, and the exposition format is four lines of spec);
* thread-safe — Spark-style executors fit from worker threads;
* labels are kwargs at observation time; each label-set gets its own child
  series, exactly Prometheus' data model.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# Latency-oriented default buckets (seconds): sub-ms compile-cache hits up
# to multi-minute full-scale fits.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    60.0, 120.0, 300.0,
)


def _escape_label_value(value: str) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace("\n", "\\n")
        .replace('"', '\\"')
    )


def _format_value(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        # a diverged fit publishes a NaN loss; the exposition format has a
        # word for it, and int(nan) below would fail the whole scrape
        return "NaN"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Metric:
    """Base: one named family holding one child per label-value tuple."""

    kind = "untyped"

    def __init__(self, name: str, help_text: str, labelnames: Tuple[str, ...]):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        self.name = name
        self.help = help_text
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} expects labels {self.labelnames}, "
                f"got {tuple(sorted(labels))}"
            )
        return tuple(str(labels[ln]) for ln in self.labelnames)

    def _child(self, labels: Dict[str, str]):
        key = self._key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._new_child()
                self._children[key] = child
            return child

    def _new_child(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def _samples(self) -> Iterable[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return list(self._children.items())

    def _label_dict(self, key: Tuple[str, ...]) -> Dict[str, str]:
        return dict(zip(self.labelnames, key))


class Counter(_Metric):
    """Monotonically increasing count (``.inc(amount, **labels)``)."""

    kind = "counter"

    class _Child:
        __slots__ = ("value", "lock")

        def __init__(self):
            self.value = 0.0
            self.lock = threading.Lock()

    def _new_child(self):
        return Counter._Child()

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        child = self._child(labels)
        with child.lock:
            child.value += amount

    def value(self, **labels) -> float:
        child = self._child(labels)
        with child.lock:
            return child.value

    def total(self) -> float:
        """Sum across every labeled child — the family-wide count,
        without walking a full registry snapshot."""
        total = 0.0
        for _key, child in self._samples():
            with child.lock:
                total += child.value
        return total


class Gauge(_Metric):
    """Point-in-time value (``.set(v, **labels)`` / ``.inc``/``.dec``)."""

    kind = "gauge"

    class _Child:
        __slots__ = ("value", "lock")

        def __init__(self):
            self.value = 0.0
            self.lock = threading.Lock()

    def _new_child(self):
        return Gauge._Child()

    def set(self, value: float, **labels) -> None:
        child = self._child(labels)
        with child.lock:
            child.value = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        child = self._child(labels)
        with child.lock:
            child.value += amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        child = self._child(labels)
        with child.lock:
            return child.value


class Histogram(_Metric):
    """Cumulative-bucket histogram (``.observe(v, **labels)``)."""

    kind = "histogram"

    class _Child:
        __slots__ = ("counts", "sum", "count", "lock")

        def __init__(self, n_buckets: int):
            self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
            self.sum = 0.0
            self.count = 0
            self.lock = threading.Lock()

    def __init__(
        self,
        name: str,
        help_text: str,
        labelnames: Tuple[str, ...] = (),
        buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
    ):
        super().__init__(name, help_text, labelnames)
        bounds = tuple(sorted(float(b) for b in buckets))
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.buckets = bounds

    def _new_child(self):
        return Histogram._Child(len(self.buckets))

    def observe(self, value: float, **labels) -> None:
        child = self._child(labels)
        with child.lock:
            child.sum += float(value)
            child.count += 1
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    child.counts[i] += 1
                    break

    def snapshot_child(self, **labels) -> Dict[str, object]:
        child = self._child(labels)
        with child.lock:
            cumulative = {}
            running = 0
            for bound, c in zip(self.buckets, child.counts):
                running += c
                cumulative[_format_value(bound)] = running
            cumulative["+Inf"] = child.count
            return {
                "count": child.count,
                "sum": child.sum,
                "buckets": cumulative,
            }


class Summary(_Metric):
    """Quantile summary backed by a mergeable streaming sketch.

    Where ``Histogram`` answers with fixed-bucket counts, ``Summary``
    answers with true quantiles at a documented relative error
    (``obs.quantiles.QuantileSketch``, DDSketch-style): ``observe`` is
    O(1), ``quantile(q)`` is exact-rank over log buckets. The Prometheus
    exposition emits ``name{quantile="0.5"}``-style lines (summary type)
    alongside whatever ``_bucket`` series the histograms export.

    ``observe(value, trace_id=...)`` additionally files a **trace-id
    exemplar**: each child keeps the ``EXEMPLAR_CAPACITY`` slowest
    observations with their trace ids, so "the p99 got worse" comes with
    the exact requests to go look at. Exemplars appear in ``snapshot()``
    and as ``# exemplar: <name>{labels} trace_id="..."`` comment lines
    in the text exposition (comments, because the endpoint advertises
    text format 0.0.4 — inline OpenMetrics ``# {...}`` annotations would
    abort a 0.0.4 scrape).
    """

    kind = "summary"
    DEFAULT_QUANTILES: Tuple[float, ...] = (0.5, 0.95, 0.99)
    EXEMPLAR_CAPACITY = 5

    class _Child:
        __slots__ = ("sketch", "exemplars", "lock")

        def __init__(self, alpha: float, max_bins: int):
            from spark_rapids_ml_tpu.obs.quantiles import QuantileSketch

            self.sketch = QuantileSketch(alpha=alpha, max_bins=max_bins)
            # slowest-N ring: [(value, trace_id, unix_ts)] kept sorted
            # ascending so [0] is the cheapest candidate to evict
            self.exemplars: List[Tuple[float, str, float]] = []
            self.lock = threading.Lock()

    def __init__(
        self,
        name: str,
        help_text: str,
        labelnames: Tuple[str, ...] = (),
        alpha: float = 0.01,
        max_bins: int = 4096,
        quantiles: Tuple[float, ...] = DEFAULT_QUANTILES,
    ):
        super().__init__(name, help_text, labelnames)
        self.alpha = float(alpha)
        self.max_bins = int(max_bins)
        self.quantiles = tuple(float(q) for q in quantiles)

    def _new_child(self):
        return Summary._Child(self.alpha, self.max_bins)

    def observe(self, value: float, trace_id: Optional[str] = None,
                **labels) -> None:
        child = self._child(labels)
        child.sketch.observe(value)
        if trace_id:
            self._note_exemplar(child, float(value), str(trace_id))

    def _note_exemplar(self, child: "_Child", value: float,
                       trace_id: str) -> None:
        with child.lock:
            ring = child.exemplars
            if len(ring) >= self.EXEMPLAR_CAPACITY and value <= ring[0][0]:
                return  # faster than every kept exemplar — not slowest-N
            ring.append((value, trace_id, time.time()))
            ring.sort(key=lambda e: e[0])
            if len(ring) > self.EXEMPLAR_CAPACITY:
                del ring[0]

    def exemplars(self, **labels) -> List[Dict[str, object]]:
        """The slowest-N exemplars for one label set, slowest first."""
        child = self._child(labels)
        with child.lock:
            ring = list(child.exemplars)
        return [
            {"value": v, "trace_id": tid, "unix_ts": ts}
            for v, tid, ts in reversed(ring)
        ]

    def quantile(self, q: float, **labels):
        return self._child(labels).sketch.quantile(q)

    def sketch(self, **labels):
        """The underlying ``QuantileSketch`` for one label set (merge it,
        serialize it, embed it in a bench record)."""
        return self._child(labels).sketch

    def sketch_states(self) -> List[Tuple[Dict[str, str], Dict[str, object]]]:
        """Every child's serialized sketch state as
        ``[(labels, state), ...]`` — the fleet-export transport
        (``obs.federation``): states merge losslessly across hosts
        where already-computed percentiles could only be averaged."""
        return [
            (self._label_dict(key), child.sketch.to_dict())
            for key, child in self._samples()
        ]

    def snapshot_child(self, **labels) -> Dict[str, object]:
        sketch = self._child(labels).sketch
        return {
            "count": sketch.count,
            "sum": sketch.sum,
            "alpha": self.alpha,
            "quantiles": {
                _format_value(q): sketch.quantile(q) for q in self.quantiles
            },
            "exemplars": self.exemplars(**labels),
        }


class MetricsRegistry:
    """Process-wide metric family registry.

    ``counter``/``gauge``/``histogram`` are get-or-create: repeated calls
    with the same name return the SAME family (so call sites never need to
    coordinate), but a name re-registered as a different kind or label set
    is a programming error and raises.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics: Dict[str, _Metric] = {}

    def _get_or_create(self, cls, name, help_text, labelnames, **kwargs):
        labelnames = tuple(labelnames)
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls) or (
                    existing.labelnames != labelnames
                ):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind} with labels {existing.labelnames}"
                    )
                return existing
            metric = cls(name, help_text, labelnames, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(self, name, help_text="", labelnames=()) -> Counter:
        return self._get_or_create(Counter, name, help_text, labelnames)

    def gauge(self, name, help_text="", labelnames=()) -> Gauge:
        return self._get_or_create(Gauge, name, help_text, labelnames)

    def histogram(
        self, name, help_text="", labelnames=(), buckets=DEFAULT_BUCKETS
    ) -> Histogram:
        return self._get_or_create(
            Histogram, name, help_text, labelnames, buckets=buckets
        )

    def summary(
        self, name, help_text="", labelnames=(), alpha=0.01,
        max_bins=4096, quantiles=Summary.DEFAULT_QUANTILES,
    ) -> Summary:
        return self._get_or_create(
            Summary, name, help_text, labelnames, alpha=alpha,
            max_bins=max_bins, quantiles=quantiles,
        )

    def reset(self) -> None:
        """Drop every family (tests / fresh bench windows)."""
        with self._lock:
            self._metrics.clear()

    def families(self):
        with self._lock:
            return list(self._metrics.values())

    # -- exposition --------------------------------------------------------

    def snapshot(self) -> Dict[str, object]:
        """JSON-safe snapshot of every series (embedded in bench records)."""
        out: Dict[str, object] = {}
        for metric in self.families():
            samples = []
            for key, _child in metric._samples():
                labels = metric._label_dict(key)
                if isinstance(metric, (Histogram, Summary)):
                    samples.append(
                        {"labels": labels,
                         **metric.snapshot_child(**labels)}
                    )
                else:
                    samples.append(
                        {"labels": labels, "value": metric.value(**labels)}
                    )
            out[metric.name] = {
                "type": metric.kind,
                "help": metric.help,
                "samples": samples,
            }
        return out

    def to_json(self, **dumps_kwargs) -> str:
        return json.dumps(self.snapshot(), **dumps_kwargs)

    def prometheus_text(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines = []
        for metric in self.families():
            if metric.help:
                lines.append(f"# HELP {metric.name} {metric.help}")
            lines.append(f"# TYPE {metric.name} {metric.kind}")
            for key, _child in metric._samples():
                labels = metric._label_dict(key)
                label_str = ",".join(
                    f'{k}="{_escape_label_value(v)}"'
                    for k, v in labels.items()
                )
                if isinstance(metric, Histogram):
                    snap = metric.snapshot_child(**labels)
                    for le, cum in snap["buckets"].items():
                        bl = (label_str + "," if label_str else "") + \
                            f'le="{le}"'
                        lines.append(
                            f"{metric.name}_bucket{{{bl}}} {cum}"
                        )
                    suffix = f"{{{label_str}}}" if label_str else ""
                    lines.append(
                        f"{metric.name}_sum{suffix} "
                        f"{_format_value(snap['sum'])}"
                    )
                    lines.append(
                        f"{metric.name}_count{suffix} {snap['count']}"
                    )
                elif isinstance(metric, Summary):
                    snap = metric.snapshot_child(**labels)
                    emitted = []
                    for q, value in snap["quantiles"].items():
                        if value is None:
                            continue
                        ql = (label_str + "," if label_str else "") + \
                            f'quantile="{q}"'
                        emitted.append(
                            f"{metric.name}{{{ql}}} {_format_value(value)}"
                        )
                    lines.extend(emitted)
                    exemplars = snap.get("exemplars") or []
                    if emitted and exemplars:
                        # The slowest observation's trace id — "p99 got
                        # worse" names the request to go look at. Emitted
                        # as a COMMENT line: inline `# {...}` exemplar
                        # annotations are only legal in the OpenMetrics
                        # exposition, and this endpoint advertises text
                        # format 0.0.4, whose parser would abort the
                        # whole scrape on one. Comments pass every 0.0.4
                        # parser untouched.
                        ex = exemplars[0]
                        suffix = f"{{{label_str}}}" if label_str else ""
                        lines.append(
                            f"# exemplar: {metric.name}{suffix} "
                            f'trace_id='
                            f'"{_escape_label_value(ex["trace_id"])}" '
                            f'{_format_value(ex["value"])} '
                            f'{ex["unix_ts"]:.3f}'
                        )
                    suffix = f"{{{label_str}}}" if label_str else ""
                    lines.append(
                        f"{metric.name}_sum{suffix} "
                        f"{_format_value(snap['sum'])}"
                    )
                    lines.append(
                        f"{metric.name}_count{suffix} {snap['count']}"
                    )
                else:
                    suffix = f"{{{label_str}}}" if label_str else ""
                    lines.append(
                        f"{metric.name}{suffix} "
                        f"{_format_value(metric.value(**labels))}"
                    )
        return "\n".join(lines) + ("\n" if lines else "")


_default_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry every instrumented fit writes to."""
    return _default_registry


def start_prometheus_server(
    port: int = 0,
    addr: str = "127.0.0.1",
    registry: Optional[MetricsRegistry] = None,
):
    """Serve ``GET /metrics`` on a daemon thread; returns the HTTPServer.

    The scrape-endpoint helper for long-lived serving processes: bind port 0
    for an ephemeral port (``server.server_address[1]``), call
    ``server.shutdown()`` to stop. Registry defaults to the process-wide one.
    """
    import http.server
    import socketserver

    reg = registry or get_registry()

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - http.server API
            if self.path.split("?")[0] not in ("/metrics", "/"):
                self.send_error(404)
                return
            body = reg.prometheus_text().encode("utf-8")
            self.send_response(200)
            self.send_header(
                "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # silence per-request stderr noise
            pass

    class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True
        allow_reuse_address = True

    server = _Server((addr, port), _Handler)
    thread = threading.Thread(
        target=server.serve_forever, name="sparkml-metrics", daemon=True
    )
    thread.start()
    return server
