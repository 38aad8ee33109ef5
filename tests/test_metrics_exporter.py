"""Metrics exposition under concurrency (ISSUE 5 satellite): the
standalone Prometheus exporter and the serve server's /metrics scraped
from multiple threads while traffic mutates the registry — every scrape
is a complete, well-formed exposition (no torn lines), trace-id exemplar
annotations stay stable, and every HTTP response (including the
429/504/404 error paths) carries an explicit Content-Length."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from spark_rapids_ml_tpu.obs.metrics import (
    MetricsRegistry,
    start_prometheus_server,
)
from spark_rapids_ml_tpu.serve import (
    ModelRegistry,
    ServeEngine,
    start_serve_server,
)

# Strict text format 0.0.4: every line is a comment or `name{labels}
# value` — nothing after the value (an inline OpenMetrics `# {...}`
# annotation would abort a 0.0.4 scrape).
_LINE_RE = re.compile(
    r"^(#.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+"
    r")$"
)
# Trace-id exemplars ride as COMMENT lines in a fixed shape.
_EXEMPLAR_RE = re.compile(
    r"^# exemplar: [a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"trace_id=\"[0-9a-f]+\" [^ ]+ [0-9.]+$"
)


def _assert_well_formed(text: str):
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _LINE_RE.match(line), f"torn/malformed line: {line!r}"
        if line.startswith("# exemplar:"):
            assert _EXEMPLAR_RE.match(line), f"bad exemplar line: {line!r}"


# -- the slowest-N exemplar ring (unit) -------------------------------------


def test_summary_exemplars_keep_slowest_n():
    reg = MetricsRegistry()
    summary = reg.summary("t_latency", "test", ("algo",))
    for i in range(10):
        summary.observe(float(i), trace_id=f"{i:032x}", algo="a")
    exemplars = summary.exemplars(algo="a")
    assert [e["value"] for e in exemplars] == [9.0, 8.0, 7.0, 6.0, 5.0]
    assert exemplars[0]["trace_id"] == f"{9:032x}"  # slowest named first
    # a faster observation never evicts a kept slow one
    summary.observe(0.5, trace_id="f" * 32, algo="a")
    assert [e["value"] for e in summary.exemplars(algo="a")] == \
        [9.0, 8.0, 7.0, 6.0, 5.0]
    # observations without a trace id feed the sketch, not the ring
    summary.observe(100.0, algo="a")
    assert summary.exemplars(algo="a")[0]["value"] == 9.0
    assert summary.sketch(algo="a").count == 12


def test_summary_exemplars_in_snapshot_and_text():
    reg = MetricsRegistry()
    summary = reg.summary("t_latency", "test latency", ("algo",))
    summary.observe(0.25, trace_id="ab" * 16, algo="pca")
    snap = reg.snapshot()["t_latency"]["samples"][0]
    assert snap["exemplars"] == [
        {"value": 0.25, "trace_id": "ab" * 16,
         "unix_ts": pytest.approx(time.time(), abs=60)},
    ]
    text = reg.prometheus_text()
    _assert_well_formed(text)
    assert (f'# exemplar: t_latency{{algo="pca"}} '
            f'trace_id="{"ab" * 16}" 0.25') in text


# -- standalone exporter under concurrent scrape + write --------------------


def test_prometheus_exporter_concurrent_scrapes_not_torn():
    reg = MetricsRegistry()
    counter = reg.counter("t_requests_total", "reqs", ("path",))
    summary = reg.summary("t_latency_seconds", "lat", ("path",))
    server = start_prometheus_server(registry=reg)
    port = server.server_address[1]
    stop = threading.Event()
    errors = []

    def writer(k):
        i = 0
        while not stop.is_set():
            counter.inc(path=f"/p{k}")
            summary.observe(0.001 * (i % 50),
                            trace_id=f"{i:032x}", path=f"/p{k}")
            i += 1

    def scraper():
        try:
            for _ in range(20):
                text = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10
                ).read().decode()
                _assert_well_formed(text)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    writers = [threading.Thread(target=writer, args=(k,))
               for k in range(4)]
    scrapers = [threading.Thread(target=scraper) for _ in range(4)]
    for t in writers + scrapers:
        t.start()
    for t in scrapers:
        t.join()
    stop.set()
    for t in writers:
        t.join()
    server.shutdown()
    assert not errors, errors[0]


# -- the serve server's /metrics under traffic ------------------------------


class _Echo:
    def transform(self, matrix):
        return np.asarray(matrix)


@pytest.fixture
def echo_server():
    reg = ModelRegistry()
    reg.register("echo_exp", _Echo())
    engine = ServeEngine(reg, max_batch_rows=32, max_wait_ms=1)
    server = start_serve_server(engine)
    try:
        yield engine, server
    finally:
        server.shutdown()
        engine.shutdown()


def test_serve_metrics_under_concurrent_traffic(echo_server):
    engine, server = echo_server
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    errors = []
    stop = threading.Event()

    def traffic():
        body = json.dumps({"model": "echo_exp",
                           "rows": [[1.0, 2.0]]}).encode()
        while not stop.is_set():
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/predict", data=body), timeout=10).read()
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)
                return

    def scraper():
        try:
            for _ in range(15):
                resp = urllib.request.urlopen(f"{base}/metrics",
                                              timeout=10)
                text = resp.read().decode()
                assert int(resp.headers["Content-Length"]) == \
                    len(text.encode())
                _assert_well_formed(text)
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    drivers = [threading.Thread(target=traffic) for _ in range(3)]
    scrapers = [threading.Thread(target=scraper) for _ in range(3)]
    for t in drivers + scrapers:
        t.start()
    for t in scrapers:
        t.join()
    stop.set()
    for t in drivers:
        t.join()
    assert not errors, errors[0]
    # exemplar lines from the traffic are present and stable in format
    text = urllib.request.urlopen(f"{base}/metrics",
                                  timeout=10).read().decode()
    exemplar_lines = [ln for ln in text.splitlines()
                      if ln.startswith("# exemplar:")]
    assert exemplar_lines
    for ln in exemplar_lines:
        assert _EXEMPLAR_RE.match(ln)


# -- Content-Length audit on the error paths --------------------------------


def _assert_error_reply_has_length(err: urllib.error.HTTPError):
    body = err.read()
    assert err.headers.get("Content-Length") is not None
    assert int(err.headers["Content-Length"]) == len(body)
    json.loads(body)  # the error body is well-formed JSON too


def test_unknown_paths_never_mint_metric_children(echo_server):
    """Arbitrary client URLs (scanners probing /wp-admin, /.env, ...)
    must collapse to one "(unknown)" path label — the raw path would be
    an unbounded label-cardinality leak in a process-lifetime registry."""
    from spark_rapids_ml_tpu.obs import get_registry

    def path_labels() -> set:
        family = get_registry().snapshot().get(
            "sparkml_http_requests_total", {"samples": []})
        return {s["labels"]["path"] for s in family["samples"]}

    engine, server = echo_server
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    # the registry is process-wide and other suites hit real endpoints, so
    # the closed set is on what THESE requests mint, whatever ran before
    before = path_labels()
    for probe in ("/wp-admin", "/.env", "/scan123", "/a?b=c"):
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + probe, timeout=30)
    with pytest.raises(urllib.error.HTTPError):  # POST side too
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/postscan", data=b"{}"), timeout=30)
    after = path_labels()
    assert "(unknown)" in after
    assert after - before <= {"(unknown)"}, after - before


def test_404_and_400_replies_carry_content_length(echo_server):
    engine, server = echo_server
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/predict",
            data=json.dumps({"model": "ghost",
                             "rows": [[1.0]]}).encode()), timeout=30)
    assert err.value.code == 404
    _assert_error_reply_has_length(err.value)
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/predict", data=b"not json"), timeout=30)
    assert err.value.code == 400
    _assert_error_reply_has_length(err.value)
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(f"{base}/nope", timeout=30)
    assert err.value.code == 404
    _assert_error_reply_has_length(err.value)


class _Slow:
    def __init__(self, delay):
        self.delay = delay

    def transform(self, matrix):
        time.sleep(self.delay)
        return np.asarray(matrix)


def test_429_and_504_replies_carry_content_length():
    reg = ModelRegistry()
    reg.register("slow_exp", _Slow(0.3))
    engine = ServeEngine(reg, max_batch_rows=2, max_wait_ms=1,
                         max_queue_depth=1)
    server = start_serve_server(engine)
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    body = json.dumps({"model": "slow_exp",
                       "rows": [[1.0, 2.0], [3.0, 4.0]]}).encode()
    try:
        plugs = [threading.Thread(target=lambda: urllib.request.urlopen(
            urllib.request.Request(f"{base}/predict", data=body),
            timeout=30).read()) for _ in range(2)]
        plugs[0].start()
        time.sleep(0.08)   # first executing
        plugs[1].start()
        time.sleep(0.08)   # second queued: depth == max_queue_depth
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/predict", data=body), timeout=30)
        assert err.value.code == 429
        _assert_error_reply_has_length(err.value)
        for t in plugs:
            t.join()
        # 504: a deadline far shorter than the model's execution
        slow_body = json.dumps({
            "model": "slow_exp",
            "rows": [[1.0, 2.0], [3.0, 4.0]],
            "deadline_ms": 40,
        }).encode()
        plug = threading.Thread(target=lambda: urllib.request.urlopen(
            urllib.request.Request(f"{base}/predict", data=body),
            timeout=30).read())
        plug.start()
        time.sleep(0.08)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/predict", data=slow_body), timeout=30)
        assert err.value.code == 504
        _assert_error_reply_has_length(err.value)
        plug.join()
    finally:
        server.shutdown()
        engine.shutdown()
