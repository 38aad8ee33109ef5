"""Metrics registry: counters/gauges/histograms, exposition, thread safety."""

import json
import threading
import urllib.request

import pytest

from spark_rapids_ml_tpu.obs.metrics import (
    MetricsRegistry,
    get_registry,
    start_prometheus_server,
)


def test_counter_labels_and_values():
    reg = MetricsRegistry()
    c = reg.counter("t_fits_total", "fits", ("algo",))
    c.inc(algo="pca")
    c.inc(2, algo="pca")
    c.inc(algo="kmeans")
    assert c.value(algo="pca") == 3.0
    assert c.value(algo="kmeans") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, algo="pca")


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("t_devices", "devices", ("platform",))
    g.set(8, platform="cpu")
    g.inc(platform="cpu")
    g.dec(2, platform="cpu")
    assert g.value(platform="cpu") == 7.0


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("t_lat", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot_child()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(55.55)
    assert snap["buckets"] == {"0.1": 1, "1": 2, "10": 3, "+Inf": 4}


def test_get_or_create_same_family_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("t_same", "x", ("l",))
    b = reg.counter("t_same", "x", ("l",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("t_same", "x", ("l",))
    with pytest.raises(ValueError):
        reg.counter("t_same", "x", ("other",))


def test_label_mismatch_rejected():
    reg = MetricsRegistry()
    c = reg.counter("t_labels", "x", ("algo",))
    with pytest.raises(ValueError):
        c.inc(wrong="pca")
    with pytest.raises(ValueError):
        c.inc()  # missing label


def test_snapshot_is_json_safe():
    reg = MetricsRegistry()
    reg.counter("t_c", "c", ("a",)).inc(a="x")
    reg.histogram("t_h", "h").observe(0.2)
    doc = json.loads(json.dumps(reg.snapshot()))
    assert doc["t_c"]["type"] == "counter"
    assert doc["t_c"]["samples"][0] == {"labels": {"a": "x"}, "value": 1.0}
    assert doc["t_h"]["samples"][0]["count"] == 1


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("t_total", "help text", ("algo",)).inc(5, algo='p"c\\a')
    reg.histogram("t_sec", "h", buckets=(1.0,)).observe(0.5)
    text = reg.prometheus_text()
    assert "# HELP t_total help text" in text
    assert "# TYPE t_total counter" in text
    # label escaping: quote and backslash
    assert 't_total{algo="p\\"c\\\\a"} 5' in text
    assert 't_sec_bucket{le="1"} 1' in text
    assert "t_sec_sum 0.5" in text
    assert "t_sec_count 1" in text


def test_a_nan_gauge_does_not_take_the_scrape_down():
    """A diverged fit publishes a NaN loss (``sparkml_fit_convergence``);
    every other series of the process must still be scraped."""
    reg = MetricsRegistry()
    reg.gauge("t_loss", "loss").set(float("nan"))
    reg.gauge("t_up", "up").set(float("-inf"))
    reg.counter("t_total", "n").inc(3)
    text = reg.prometheus_text()
    assert "t_loss NaN" in text
    assert "t_up -Inf" in text
    assert "t_total 3" in text


def test_thread_safety_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("t_conc", "x", ("t",))

    def worker():
        for _ in range(1000):
            c.inc(t="shared")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(t="shared") == 8000.0


def test_default_registry_is_process_wide():
    assert get_registry() is get_registry()


def test_prometheus_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("t_http_total", "x").inc(3)
    server = start_prometheus_server(port=0, registry=reg)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "t_http_total 3" in body
    finally:
        server.shutdown()
        server.server_close()
