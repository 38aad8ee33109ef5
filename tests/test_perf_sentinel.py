"""Perf sentinel verdicts (scripts/perf_sentinel.py): PASS / REGRESSED /
STALE / NO_BASELINE over fixture histories, including a driver-wrapper
CPU-fallback record against a chip-measured history."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
from perf_sentinel import (  # noqa: E402
    EXIT_CODES,
    extract_record,
    iter_history,
    judge,
    judge_percentiles,
    judge_record,
    load_candidate,
    noise_band,
    record_percentiles,
    stale_baseline_age_days,
)

sys.path.pop(0)

METRIC = "PCA.fit rows/sec/chip (1000x100, k=10)"


def _history(*values, platform="tpu", metric=METRIC):
    return [
        {"metric": metric, "value": v, "unit": "rows/sec",
         "platform": platform, "_source": f"fixture{i}.json"}
        for i, v in enumerate(values)
    ]


def _record(value, platform="tpu", **extra):
    rec = {"metric": METRIC, "value": value, "unit": "rows/sec",
           "platform": platform}
    rec.update(extra)
    return rec


def test_pass_within_band():
    v = judge(_record(96_000.0), _history(100_000.0, 102_000.0, 98_000.0))
    assert v["verdict"] == "PASS"
    assert v["baseline"]["n_samples"] == 3
    assert v["band"]["low"] < 96_000.0 < v["band"]["high"]


def test_pass_when_faster_than_baseline():
    v = judge(_record(150_000.0), _history(100_000.0))
    assert v["verdict"] == "PASS"


def test_regressed_below_band():
    v = judge(_record(50_000.0), _history(100_000.0, 101_000.0))
    assert v["verdict"] == "REGRESSED"
    assert "below the noise band" in v["reason"]
    assert EXIT_CODES[v["verdict"]] == 1


def test_regressed_direction_flips_for_seconds():
    hist = [
        {"metric": "DBSCAN.fit seconds", "value": 10.0, "unit": "seconds",
         "platform": "tpu", "_source": "fixture.json"},
    ]
    slow = judge({"metric": "DBSCAN.fit seconds", "value": 30.0,
                  "unit": "seconds", "platform": "tpu"}, hist)
    assert slow["verdict"] == "REGRESSED"
    fast = judge({"metric": "DBSCAN.fit seconds", "value": 5.0,
                  "unit": "seconds", "platform": "tpu"}, hist)
    assert fast["verdict"] == "PASS"


def test_stale_on_fallback_record():
    """A CPU fallback run never reads as a regression of the chip
    baseline — it reads as a stale baseline."""
    rec = _record(
        3_000.0, platform="cpu",
        fallback_reason="backend init exceeded 60.0s",
    )
    v = judge(rec, _history(2_000_000.0))
    assert v["verdict"] == "STALE"
    assert "stale" in v["reason"]
    assert v["stale_baseline"]["value"] == 2_000_000.0
    assert EXIT_CODES[v["verdict"]] == 2


def test_stale_on_platform_mismatch_without_fallback_marker():
    v = judge(_record(3_000.0, platform="cpu"), _history(2_000_000.0))
    assert v["verdict"] == "STALE"


def test_stale_verdict_carries_baseline_age_warning():
    """Staleness as a NUMBER: a CPU-fallback round against a
    dated chip baseline states how many days the baseline has gone
    un-re-measured, not just prose."""
    history = _history(2_000_000.0)
    history[0]["measured_utc"] = "2026-01-15T00:00:00Z"
    rec = _record(3_000.0, platform="cpu",
                  fallback_reason="device backend hung")
    v = judge(rec, history)
    assert v["verdict"] == "STALE"
    assert v["stale_baseline_age_days"] > 100  # Jan 2026 vs today
    assert "days old" in v["stale_warning"]
    assert "fell back to CPU" in v["stale_warning"]


def test_stale_age_helper_parses_and_degrades():
    # Z-suffix and explicit-offset spellings both parse
    day = stale_baseline_age_days(
        {"measured_utc": "2026-01-01T00:00:00Z"},
        now=1767225600.0 + 86400.0)  # 2026-01-02T00:00:00Z
    assert day == pytest.approx(1.0, abs=0.01)
    assert stale_baseline_age_days(
        {"measured_utc": "2026-01-01T00:00:00+00:00"},
        now=1767225600.0) == pytest.approx(0.0, abs=0.01)
    # malformed / absent timestamps degrade to None, never raise
    assert stale_baseline_age_days({"measured_utc": "not a date"}) is None
    assert stale_baseline_age_days({}) is None
    assert stale_baseline_age_days(None) is None
    # a STALE verdict without a parseable stamp omits the age fields
    v = judge(_record(3_000.0, platform="cpu"), _history(2_000_000.0))
    assert v["verdict"] == "STALE"
    assert "stale_baseline_age_days" not in v


def test_stale_warning_wording_distinguishes_mismatch_from_fallback():
    """A deliberately-CPU round (platform mismatch, no backend failure)
    must not claim the round fell back."""
    history = _history(2_000_000.0)
    history[0]["measured_utc"] = "2026-01-15T00:00:00Z"
    v = judge(_record(3_000.0, platform="cpu"), history)
    assert v["verdict"] == "STALE"
    assert "fell back" not in v["stale_warning"]
    assert "ran on cpu" in v["stale_warning"]


def test_cpu_history_comparable_for_cpu_record():
    """With a CPU-only history, a CPU record is a real comparison."""
    v = judge(_record(900.0, platform="cpu"),
              _history(1_000.0, platform="cpu"))
    assert v["verdict"] == "PASS"
    v = judge(_record(100.0, platform="cpu"),
              _history(1_000.0, platform="cpu"))
    assert v["verdict"] == "REGRESSED"


def test_no_baseline():
    v = judge({"metric": "unseen metric", "value": 1.0, "unit": "rows/sec",
               "platform": "tpu"}, _history(5.0))
    assert v["verdict"] == "NO_BASELINE"
    assert EXIT_CODES[v["verdict"]] == 3


def test_noise_band_widens_with_spread():
    assert noise_band([100.0], 0.15) == 0.15
    wide = noise_band([100.0, 60.0, 140.0, 80.0, 120.0], 0.15)
    assert wide > 0.15


def test_extract_record_shapes():
    raw = {"metric": "m", "value": 1.0}
    assert extract_record(raw) == raw
    assert extract_record({"parsed": raw}) == raw
    assert extract_record({"headline": raw}) == raw
    assert extract_record({"tail": "text"}) is None


def test_load_candidate_json_lines(tmp_path):
    path = tmp_path / "rec.json"
    path.write_text(
        '# comment\n{"not_a_record": true}\n'
        '{"metric": "m1", "value": 1.0}\n{"metric": "m2", "value": 2.0}\n'
    )
    rec = load_candidate(str(path))
    assert rec["metric"] == "m2"  # last record line wins


def test_iter_history_reads_repo_shapes(tmp_path):
    (tmp_path / "records" / "r1").mkdir(parents=True)
    (tmp_path / "BENCH_MEASURED.json").write_text(json.dumps({
        "note": "x",
        "headline": {"metric": "m", "value": 10.0, "platform": "tpu"},
        "sub": {"metric": "m2", "value": 5.0, "platform": "tpu"},
    }))
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "parsed": {"metric": "m", "value": 9.0, "platform": "tpu"},
    }))
    (tmp_path / "records" / "r1" / "bench.json").write_text(
        '{"metric": "m", "value": 11.0, "platform": "tpu"}\n'
    )
    hist = iter_history(str(tmp_path))
    values = sorted(h["value"] for h in hist if h["metric"] == "m")
    assert values == [9.0, 10.0, 11.0]
    assert any(h["metric"] == "m2" for h in hist)
    # exclusion: the candidate file is never its own baseline
    hist2 = iter_history(str(tmp_path),
                         exclude=str(tmp_path / "BENCH_r01.json"))
    assert sorted(h["value"] for h in hist2 if h["metric"] == "m") == \
        [10.0, 11.0]


def test_cli_on_driver_wrapper_cpu_fallback_record(tmp_path):
    """Acceptance: a driver wrapper (``{"parsed": ...}``) holding a CPU
    fallback of a chip metric, judged against a chip-measured history,
    emits a structured STALE verdict — not REGRESSED."""
    metric = "PCA.fit rows/sec/chip (10485760x4096, k=256)"
    (tmp_path / "BENCH_MEASURED.json").write_text(json.dumps({
        "headline": {"metric": metric, "value": 2_059_608.0,
                     "unit": "rows/sec", "platform": "tpu",
                     "measured_utc": "2026-07-31T01:20:19Z"},
    }))
    target = tmp_path / "BENCH_r05.json"
    target.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 0,
        "parsed": {"metric": metric, "value": 3031.0, "unit": "rows/sec",
                   "platform": "cpu", "device_kind": "cpu",
                   "fallback_reason": "backend init exceeded 60.0s"},
    }))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_sentinel.py"),
         str(target), "--history-root", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout)
    assert verdict["verdict"] == "STALE"
    assert verdict["stale_baseline"]["value"] > verdict["value"]


def test_cli_regressed_vs_stale_distinguished(tmp_path):
    """A genuinely slower chip run is REGRESSED; the same value as a CPU
    fallback is STALE — the two states never conflate."""
    (tmp_path / "BENCH_MEASURED.json").write_text(json.dumps({
        "headline": {"metric": METRIC, "value": 2_000_000.0,
                     "unit": "rows/sec", "platform": "tpu"},
    }))
    script = os.path.join(REPO, "scripts", "perf_sentinel.py")

    slow_chip = tmp_path / "slow_chip.json"
    slow_chip.write_text(json.dumps(_record(500_000.0)))
    proc = subprocess.run(
        [sys.executable, script, str(slow_chip),
         "--history-root", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert json.loads(proc.stdout)["verdict"] == "REGRESSED"
    assert proc.returncode == 1

    fallback = tmp_path / "fallback.json"
    fallback.write_text(json.dumps(_record(
        500_000.0, platform="cpu", fallback_reason="wedged")))
    proc = subprocess.run(
        [sys.executable, script, str(fallback),
         "--history-root", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert json.loads(proc.stdout)["verdict"] == "STALE"
    assert proc.returncode == 2


# -- latency-percentile records (serving quantile-sketch output) -----------

LAT_METRIC = "pca.transform seconds/batch (4096x256)"


def _pct_history(*pcts, platform="tpu", metric=LAT_METRIC):
    return [
        {"metric": metric, "unit": "seconds", "platform": platform,
         "percentiles": dict(p), "_source": f"pfix{i}.json"}
        for i, p in enumerate(pcts)
    ]


def _pct_record(p50, p95, p99, platform="tpu", **extra):
    rec = {"metric": LAT_METRIC, "unit": "seconds", "platform": platform,
           "percentiles": {"p50": p50, "p95": p95, "p99": p99}}
    rec.update(extra)
    return rec


def test_record_percentiles_extraction():
    assert record_percentiles(_pct_record(0.01, 0.02, 0.03)) == {
        "p50": 0.01, "p95": 0.02, "p99": 0.03}
    # top-level keys work too, and override the nested dict
    rec = _pct_record(0.01, 0.02, 0.03)
    rec["p99"] = 0.5
    assert record_percentiles(rec)["p99"] == 0.5
    assert record_percentiles({"metric": "m", "value": 1.0}) == {}


def test_percentile_pass_within_band():
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030},
                        {"p50": 0.011, "p95": 0.019, "p99": 0.031},
                        {"p50": 0.010, "p95": 0.021, "p99": 0.029})
    v = judge_record(_pct_record(0.0105, 0.0205, 0.0305), hist)
    assert v["verdict"] == "PASS"
    assert set(v["percentiles"]) == {"p50", "p95", "p99"}
    assert all(s["verdict"] == "PASS" for s in v["percentiles"].values())


def test_tail_regression_cannot_hide_behind_healthy_mean():
    """The satellite case: p50 healthy, p99 3x worse -> REGRESSED, and the
    sub-verdict names the offending percentile."""
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030},
                        {"p50": 0.010, "p95": 0.020, "p99": 0.030})
    v = judge_record(_pct_record(0.010, 0.020, 0.090), hist)
    assert v["verdict"] == "REGRESSED"
    assert v["percentiles"]["p50"]["verdict"] == "PASS"
    assert v["percentiles"]["p99"]["verdict"] == "REGRESSED"
    assert "p99: REGRESSED" in v["reason"]
    assert EXIT_CODES[v["verdict"]] == 1


def test_percentile_latency_lower_is_better():
    """Latency percentiles judge in seconds: a FASTER p99 passes, never
    regresses."""
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030})
    v = judge_record(_pct_record(0.002, 0.004, 0.006), hist)
    assert v["verdict"] == "PASS"


def test_percentile_no_baseline_and_scalar_mix():
    v = judge_percentiles(_pct_record(0.01, 0.02, 0.03), [])
    assert v["verdict"] == "NO_BASELINE"
    # a percentile record with a scalar value judges the scalar too
    hist = _history(100_000.0, metric=LAT_METRIC)
    rec = _pct_record(0.01, 0.02, 0.03, value=50_000.0,
                      )
    rec["unit"] = "rows/sec"
    v2 = judge_record(rec, hist)
    assert v2["scalar"]["verdict"] == "REGRESSED"
    assert v2["verdict"] == "REGRESSED"


def test_percentile_fallback_record_is_stale():
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030})
    v = judge_record(
        _pct_record(0.5, 0.9, 1.5, platform="cpu",
                    fallback_reason="device backend hung"),
        hist,
    )
    assert v["verdict"] == "STALE"
    assert all(s["verdict"] == "STALE" for s in v["percentiles"].values())


def test_percentile_record_via_cli(tmp_path):
    (tmp_path / "BENCH_MEASURED.json").write_text(json.dumps({
        "headline": {"metric": LAT_METRIC, "unit": "seconds",
                     "platform": "tpu",
                     "percentiles": {"p50": 0.010, "p95": 0.020,
                                     "p99": 0.030}},
    }))
    script = os.path.join(REPO, "scripts", "perf_sentinel.py")
    rec = tmp_path / "rec.json"
    rec.write_text(json.dumps(_pct_record(0.010, 0.021, 0.120)))
    proc = subprocess.run(
        [sys.executable, script, str(rec), "--history-root", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    out = json.loads(proc.stdout)
    assert out["verdict"] == "REGRESSED"
    assert out["percentiles"]["p99"]["verdict"] == "REGRESSED"
    assert proc.returncode == 1


def test_percentiles_judge_lower_is_better_even_with_throughput_unit():
    """Regression guard: a record whose SCALAR unit is rows/sec must still
    judge its latency percentiles as lower-is-better — a 3x p99 blowup
    can never read as an improvement."""
    hist = [{"metric": LAT_METRIC, "unit": "rows/sec", "platform": "tpu",
             "value": 100_000.0, "percentiles": {"p99": 0.030},
             "_source": "h.json"}]
    rec = {"metric": LAT_METRIC, "unit": "rows/sec", "platform": "tpu",
           "value": 100_500.0, "percentiles": {"p99": 0.090}}
    v = judge_record(rec, hist)
    assert v["percentiles"]["p99"]["verdict"] == "REGRESSED"
    assert v["verdict"] == "REGRESSED"
    # and a FASTER p99 under the same throughput unit passes
    rec_fast = dict(rec, percentiles={"p99": 0.010})
    assert judge_record(rec_fast, hist)["verdict"] == "PASS"


def test_percentiles_reason_names_scalar_offender():
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030}) + \
        _history(100_000.0, metric=LAT_METRIC)
    rec = _pct_record(0.010, 0.020, 0.030, value=10_000.0)
    rec["unit"] = "rows/sec"
    v = judge_record(rec, hist)
    assert v["verdict"] == "REGRESSED"
    assert "scalar: REGRESSED" in v["reason"]


def test_percentiles_lower_is_better_even_with_per_sec_metric_name():
    """Regression guard: '/sec' in the metric NAME (not just the unit)
    must not flip percentile judging back to higher-is-better."""
    metric = "pca.transform rows/sec (4096x256)"
    hist = [{"metric": metric, "unit": "rows/sec", "platform": "tpu",
             "value": 100_000.0, "percentiles": {"p99": 0.030},
             "_source": "h.json"}]
    rec = {"metric": metric, "unit": "rows/sec", "platform": "tpu",
           "value": 100_500.0, "percentiles": {"p99": 0.300}}
    v = judge_record(rec, hist)
    assert v["percentiles"]["p99"]["verdict"] == "REGRESSED"
    assert v["verdict"] == "REGRESSED"


def test_explicit_higher_is_better_flag_wins():
    from perf_sentinel import higher_is_better

    assert higher_is_better({"metric": "x rows/sec", "unit": "rows/sec",
                             "higher_is_better": False}) is False
    assert higher_is_better({"metric": "x seconds", "unit": "seconds",
                             "higher_is_better": True}) is True


def test_budget_remaining_judges_higher_is_better():
    """ISSUE 5 satellite: slo_budget_remaining is higher-is-better even
    without a '/sec' unit — and even when the unit TEXT mentions seconds
    (a budget can be phrased as seconds of allowed badness left)."""
    from perf_sentinel import higher_is_better

    assert higher_is_better({
        "metric": "serve slo_budget_remaining (6h)", "unit": "fraction",
    }) is True
    assert higher_is_better({
        "metric": "slo_budget_remaining",
        "unit": "seconds of error budget",
    }) is True
    metric = "serve slo_budget_remaining (6h)"
    hist = [{"metric": metric, "value": 0.9, "unit": "fraction",
             "platform": "tpu", "_source": "f.json"}]
    worse = judge({"metric": metric, "value": 0.2, "unit": "fraction",
                   "platform": "tpu"}, hist)
    assert worse["verdict"] == "REGRESSED"
    assert "below the noise band" in worse["reason"]
    better = judge({"metric": metric, "value": 0.99, "unit": "fraction",
                    "platform": "tpu"}, hist)
    assert better["verdict"] == "PASS"


def test_burn_rate_judges_lower_is_better():
    """slo_fast_burn_rate is budget spend SPEED: a jump to paging-level
    burn must read REGRESSED, never 'better than the band'."""
    from perf_sentinel import higher_is_better

    assert higher_is_better({
        "metric": "serve slo_fast_burn_rate (5m)", "unit": "fraction",
    }) is False
    metric = "serve slo_fast_burn_rate (5m)"
    hist = [{"metric": metric, "value": 0.1, "unit": "fraction",
             "platform": "tpu", "_source": "f.json"}]
    paging = judge({"metric": metric, "value": 20.0, "unit": "fraction",
                    "platform": "tpu"}, hist)
    assert paging["verdict"] == "REGRESSED"
    quiet = judge({"metric": metric, "value": 0.0, "unit": "fraction",
                   "platform": "tpu"}, hist)
    assert quiet["verdict"] == "PASS"


def test_malformed_percentile_fields_are_skipped_not_fatal():
    """Regression guard: a malformed percentile value in a record or the
    committed history degrades to 'field skipped', never a crash."""
    assert record_percentiles(
        {"metric": "m", "percentiles": {"p50": "n/a", "p99": 0.03}}
    ) == {"p99": 0.03}
    assert record_percentiles({"metric": "m", "p95": "bogus"}) == {}
    hist = _pct_history({"p50": 0.010, "p95": 0.020, "p99": 0.030}) + [
        {"metric": LAT_METRIC, "unit": "seconds", "platform": "tpu",
         "percentiles": {"p99": "corrupt"}, "_source": "bad.json"},
    ]
    v = judge_record(_pct_record(0.010, 0.020, 0.030), hist)
    assert v["verdict"] == "PASS"
