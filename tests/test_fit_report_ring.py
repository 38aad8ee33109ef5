"""The one door out for what fits counted: ``obs.report``'s ring of the
process's newest reports (CPU).

``_publish`` appends every fit's report to a bounded ring;
``recent_fit_reports(n, algo)`` hands back the newest ``n``, oldest first;
``last_fit_report`` is what it was (the last report an algo, kept however
many fits of other algos follow). The ring holds reports — numbers and short
strings — never a model or an array.
"""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu import PCA, obs
from spark_rapids_ml_tpu.obs import report as report_module
from spark_rapids_ml_tpu.obs.metrics import get_registry
from spark_rapids_ml_tpu.obs.report import (
    RECENT_REPORTS,
    FitReport,
    last_fit_report,
    recent_fit_reports,
)

N, BATCH, K = 16, 32, 2


@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own, as large as the process's."""
    import collections

    fresh = collections.deque(maxlen=RECENT_REPORTS)
    monkeypatch.setattr(report_module, "_recent_reports", fresh)
    monkeypatch.setattr(report_module, "_last_reports", {})
    return fresh


def _report(algo: str, i: int) -> FitReport:
    return FitReport(algo=algo, trace_id=f"{algo}-{i}", started_utc="",
                     wall_seconds=float(i))


def _publish(algo: str, i: int) -> FitReport:
    report = _report(algo, i)
    report_module._publish(report)
    return report


def _fit(rows: int = 4 * BATCH, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(rows, N)) + 1.0).astype(np.float32)
    return PCA().setK(K).set("batchRows", BATCH).set(
        "dtype", "float32").fit(iter([x]))


def test_the_newest_n_oldest_first(ring):
    published = [_publish("pca", i) for i in range(5)]
    assert recent_fit_reports() == published
    assert recent_fit_reports(2) == published[-2:]
    assert recent_fit_reports(5) == recent_fit_reports(50) == published
    assert recent_fit_reports(0) == []


def test_the_ring_is_bounded(ring):
    assert RECENT_REPORTS == 256
    published = [_publish("pca", i) for i in range(RECENT_REPORTS + 10)]
    assert len(ring) == RECENT_REPORTS
    assert recent_fit_reports() == published[10:]
    assert recent_fit_reports(3) == published[-3:]


def test_the_algo_filter_counts_that_algos_reports(ring):
    a = [_publish("pca", i) for i in range(3)]
    b = [_publish("kmeans", i) for i in range(2)]
    a.append(_publish("pca", 3))
    assert recent_fit_reports(algo="pca") == a
    assert recent_fit_reports(2, algo="kmeans") == b
    assert recent_fit_reports(1, algo="pca") == a[-1:]
    assert recent_fit_reports(algo="nothing") == []


def test_last_fit_report_is_unchanged_and_outlives_the_ring(ring):
    assert last_fit_report() is None and last_fit_report("pca") is None
    first = _publish("pca", 0)
    other = _publish("kmeans", 1)
    assert last_fit_report() is other
    assert last_fit_report("pca") is first
    assert last_fit_report("kmeans") is other
    newest = [_publish("kmeans", i) for i in range(RECENT_REPORTS)][-1]
    assert recent_fit_reports(algo="pca") == []  # the ring has forgotten
    assert last_fit_report("pca") is first  # the escape hatch has not
    assert last_fit_report() is last_fit_report("kmeans") is newest
    assert obs.last_fit_report is last_fit_report
    assert obs.recent_fit_reports is recent_fit_reports


def test_a_fits_report_is_the_rings_newest_and_holds_no_array(ring):
    model = _fit()
    (report,) = recent_fit_reports()
    assert report is model.fit_report_ is last_fit_report("pca")
    ingest = report.extra["ingest"]
    assert ingest["bytes_put"] == 4 * BATCH * N * 4
    assert ingest["per_chip"][0]["landings"] == ingest["batches"] == 4

    def leaves(value):
        if isinstance(value, dict):
            for v in value.values():
                yield from leaves(v)
        elif isinstance(value, (list, tuple)):
            for v in value:
                yield from leaves(v)
        else:
            yield value

    for leaf in leaves(report.as_dict()):
        assert leaf is None or isinstance(leaf, (bool, int, float, str)), leaf


def test_a_fit_that_raises_publishes_nothing(ring):
    with pytest.raises(ValueError):
        PCA().setK(N + 1).fit(iter([np.ones((BATCH, N), np.float32)]))
    assert recent_fit_reports() == []


def test_two_fits_at_once_both_reach_the_ring(ring):
    """Fits on more threads than the ring's lock lets in at once, with a
    short switch interval: every report arrives once, whole."""
    fits, per_thread = 6, 3
    errors = []

    def run(seed: int) -> None:
        try:
            for i in range(per_thread):
                _fit(rows=(2 + seed % 3) * BATCH, seed=seed * 10 + i)
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(s,))
                   for s in range(fits)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors
    reports = recent_fit_reports()
    assert len(reports) == fits * per_thread
    assert len({r.trace_id for r in reports}) == fits * per_thread
    for r in reports:
        ingest = r.extra["ingest"]
        assert ingest["per_chip"][0]["landings"] == ingest["batches"] \
            == r.rows // BATCH


def test_the_ingest_counters_reach_the_registry(ring):
    reg = get_registry()

    def value(name: str, **labels) -> float:
        for family in reg.families():
            if family.name == name:
                return family.value(**labels)
        return 0.0

    names = ("sparkml_ingest_bytes_put_total",
             "sparkml_ingest_put_wait_seconds_total",
             "sparkml_ingest_batches_kept_total",
             "sparkml_ingest_bytes_reblocked_total")
    before = {name: value(name, algo="pca") for name in names}
    staged = {o: value("sparkml_ingest_staging_total", algo="pca", outcome=o)
              for o in ("reused", "fresh")}
    model = _fit(rows=4 * BATCH + 5)  # a padded tail: one copied batch
    ingest = model.fit_report_.extra["ingest"]
    (chip,) = ingest["per_chip"]
    assert value(names[0], algo="pca") - before[names[0]] \
        == ingest["bytes_put"] == 5 * BATCH * N * 4
    assert value(names[1], algo="pca") - before[names[1]] \
        == pytest.approx(ingest["put_wait_seconds"])
    assert value(names[2], algo="pca") == before[names[2]]  # one pass
    assert value(names[3], algo="pca") - before[names[3]] \
        == ingest["bytes_reblocked"] > 0
    assert value("sparkml_ingest_crossing_seconds_total", algo="pca",
                 chip=chip["device"]) >= chip["crossing_seconds"] > 0
    for outcome in ("reused", "fresh"):
        assert value("sparkml_ingest_staging_total", algo="pca",
                     outcome=outcome) - staged[outcome] \
            == ingest["staging_" + outcome]
    assert ingest["staging_reused"] + ingest["staging_fresh"] == 1
    text = reg.prometheus_text()
    for name in names + ("sparkml_ingest_crossing_seconds_total",
                         "sparkml_ingest_staging_total"):
        assert name in text
