#!/usr/bin/env python
"""Offline perf-regression sentinel: verdicts over bench records.

Compares one bench record (any ``emit_record`` JSON line, a
``BENCH_r0N.json`` driver wrapper, or a ``BENCH_MEASURED*.json`` headline)
against the repo's committed measurement history (``BENCH_MEASURED*.json``,
``BENCH_r0*.json``, ``records/**/*.json``) and emits ONE structured verdict
line::

    {"verdict": "PASS|REGRESSED|STALE|NO_BASELINE", ...}

Records carrying latency-percentile fields (``p50``/``p95``/``p99`` at
top level or under ``percentiles`` — what transform bench records emit
from the serving quantile sketch) are judged **per percentile** against
the same percentile in the history, and the overall verdict is the worst
sub-verdict (tail regressions cannot hide behind a healthy mean).

Verdicts:

* **PASS** — value within (or better than) the noise band around the
  comparable baseline (same metric, same platform).
* **REGRESSED** — value worse than the band. Exit 1.
* **STALE** — the record is NOT comparable to the best-known baseline: a
  CPU fallback run (``fallback_reason`` / a ``best_known_chip_record``
  marked stale) or a platform mismatch against a chip-measured history.
  A round that could not reach the chip must read as "chip baseline is
  stale", never as a 679× regression. Exit 2.
* **NO_BASELINE** — no history for this metric at all. Exit 3.

The noise band is ``max(--tolerance, 2·MAD/median)`` over the historical
values for (metric, platform): single-sample histories fall back to the
tolerance (default 15% — measured round-to-round jitter on the chip
records), multi-sample histories widen to the observed spread.

Usage::

    python scripts/perf_sentinel.py records/bench_serve_r09.json
    python scripts/perf_sentinel.py record.json --tolerance 0.1
    some_bench | python scripts/perf_sentinel.py -
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_robust():
    """The shared robust-statistics module (``obs/robust.py``), loaded
    BY PATH: the sentinel and the online anomaly detectors must use
    the same MAD/noise-band arithmetic, but judging a JSON record must
    not import the package (and with it jax)."""
    path = os.path.join(REPO, "spark_rapids_ml_tpu", "obs", "robust.py")
    spec = importlib.util.spec_from_file_location(
        "sparkml_obs_robust", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_robust = _load_robust()

EXIT_CODES = {"PASS": 0, "REGRESSED": 1, "STALE": 2, "NO_BASELINE": 3}
DEFAULT_TOLERANCE = 0.15
PERCENTILE_KEYS = ("p50", "p95", "p99")


# -- record extraction -----------------------------------------------------


def _is_record(obj) -> bool:
    return (isinstance(obj, dict) and "metric" in obj
            and (obj.get("value") is not None
                 or record_percentiles(obj)))


def record_percentiles(record) -> Dict[str, float]:
    """The latency-percentile fields of a record: a ``percentiles`` dict
    and/or top-level ``p50``/``p95``/``p99`` keys (sketch-quantile output
    from instrumented transform benches)."""
    if not isinstance(record, dict):
        return {}
    out: Dict[str, float] = {}
    nested = record.get("percentiles")
    if isinstance(nested, dict):
        for key in PERCENTILE_KEYS:
            value = nested.get(key)
            if value is not None:
                try:
                    out[key] = float(value)
                except (TypeError, ValueError):
                    continue  # one malformed field never kills the run
    for key in PERCENTILE_KEYS:
        value = record.get(key)
        if value is not None:
            try:
                out[key] = float(value)
            except (TypeError, ValueError):
                continue
    return out


def extract_record(obj) -> Optional[Dict[str, Any]]:
    """The measurement record inside any of the repo's bench artifact
    shapes: a raw record, a BENCH_rN driver wrapper ({"parsed": ...}),
    or a BENCH_MEASURED composite ({"headline": ...})."""
    if _is_record(obj):
        return obj
    if isinstance(obj, dict):
        for key in ("parsed", "headline"):
            inner = obj.get(key)
            if _is_record(inner):
                return inner
    return None


def load_candidate(path: str) -> Dict[str, Any]:
    text = sys.stdin.read() if path == "-" else open(path).read()
    try:
        rec = extract_record(json.loads(text))
        if rec is not None:
            return rec
    except ValueError:
        pass
    # JSON-lines: last parseable record wins (emit_record's final-line
    # contract)
    rec = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            parsed = extract_record(json.loads(line))
        except ValueError:
            continue
        if parsed is not None:
            rec = parsed
    if rec is None:
        raise SystemExit(f"no bench record found in {path!r}")
    return rec


# -- history ---------------------------------------------------------------


def iter_history(root: str, exclude: Optional[str] = None
                 ) -> List[Dict[str, Any]]:
    """Every committed measurement record, tagged with its source file."""
    out: List[Dict[str, Any]] = []
    exclude_real = os.path.realpath(exclude) if exclude else None

    def _add(obj, source, when=None):
        rec = extract_record(obj)
        if rec is not None:
            entry = dict(rec)
            entry["_source"] = source
            if when and "measured_utc" not in entry:
                entry["_measured_utc"] = when
            out.append(entry)

    paths = sorted(glob.glob(os.path.join(root, "BENCH_MEASURED*.json")))
    paths += sorted(glob.glob(os.path.join(root, "BENCH_r[0-9]*.json")))
    for path in paths:
        if exclude_real and os.path.realpath(path) == exclude_real:
            continue
        try:
            doc = json.load(open(path))
        except ValueError:
            continue
        rel = os.path.relpath(path, root)
        when = doc.get("collected_utc") if isinstance(doc, dict) else None
        if isinstance(doc, dict):
            _add(doc, rel, when)
            # BENCH_MEASURED composites: every named sub-record counts
            for key, val in doc.items():
                if key in ("parsed", "headline"):
                    continue
                if _is_record(val):
                    _add(val, f"{rel}#{key}", when)
    for path in sorted(glob.glob(os.path.join(root, "records", "**",
                                              "*.json"), recursive=True)):
        if exclude_real and os.path.realpath(path) == exclude_real:
            continue
        rel = os.path.relpath(path, root)
        try:
            lines = open(path).read().splitlines()
        except OSError:
            continue
        for line in lines:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                _add(json.loads(line), rel)
            except ValueError:
                continue
    return out


# -- verdict logic ---------------------------------------------------------


def higher_is_better(record: Dict[str, Any]) -> bool:
    # An explicit flag beats the text heuristic — percentile pseudo-records
    # force lower-is-better even when the metric NAME contains "/sec".
    explicit = record.get("higher_is_better")
    if isinstance(explicit, bool):
        return explicit
    text = f"{record.get('unit', '')} {record.get('metric', '')}".lower()
    if "budget_remaining" in text:
        # SLO error budget left: more is better, despite lacking a "/sec"
        # unit — and despite any "seconds"-flavored unit text (a budget
        # can be expressed as seconds of allowed badness remaining).
        return True
    if "burn_rate" in text:
        # SLO burn rate: budget spend speed — lower is better, and the
        # throughput-style default would invert the verdict.
        return False
    if "rows/sec" in text or "/sec" in text:
        return True
    if "second" in text:
        return False
    return True  # throughput-style by default


def _median(values: List[float]) -> float:
    return _robust.median(values)


def noise_band(values: List[float], tolerance: float) -> float:
    """Relative half-width of the acceptance band around the median —
    THE shared arithmetic (``obs/robust.py``): the offline sentinel
    and the online anomaly detectors judge against the same band."""
    return _robust.noise_band(values, tolerance)


def backend_mismatch_reason(record: Dict[str, Any]) -> Optional[str]:
    """Why this record's RESOLVED backend (the ``backend`` provenance
    stamp ``emit_record`` adds) disagrees with the backend it was
    supposed to run on — None when provenance is absent (older records)
    or consistent. A mismatch means the number itself is untrustworthy,
    which is a different failure from a slow-but-honest measurement."""
    resolved = (record.get("backend") or {}).get("platform")
    if not resolved:
        return None
    resolved = str(resolved).lower()
    required = record.get("required_platform")
    if required and resolved != str(required).lower():
        return (f"record required platform {required!r} but the resolved "
                f"jax backend was {resolved!r}")
    claimed = record.get("platform")
    if claimed and str(claimed).lower() != resolved:
        return (f"record claims platform {claimed!r} but the resolved jax "
                f"backend was {resolved!r} (silent fallback)")
    return None


def _is_fallback(record: Dict[str, Any]) -> bool:
    if record.get("fallback_reason"):
        return True
    best = record.get("best_known_chip_record")
    return bool(isinstance(best, dict) and best.get("stale"))


def _parse_utc(value) -> Optional[float]:
    """Epoch seconds from an ISO-8601 UTC stamp (``...Z`` or offset
    spelled out); None when unparseable — a malformed timestamp must
    never break a verdict."""
    if not value:
        return None
    from datetime import datetime, timezone

    text = str(value).strip()
    if text.endswith("Z"):
        text = text[:-1] + "+00:00"
    try:
        dt = datetime.fromisoformat(text)
    except ValueError:
        return None
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return dt.timestamp()


def stale_baseline_age_days(stale_baseline,
                            now: Optional[float] = None
                            ) -> Optional[float]:
    """How many days old the stale chip baseline is — the number that
    turns 'STALE' from prose into an actionable age. None when the
    baseline carries no parseable measurement timestamp."""
    if not isinstance(stale_baseline, dict):
        return None
    measured = _parse_utc(stale_baseline.get("measured_utc"))
    if measured is None:
        return None
    if now is None:
        import time as _time

        now = _time.time()
    return max((now - measured) / 86400.0, 0.0)


def judge(record: Dict[str, Any], history: List[Dict[str, Any]],
          tolerance: float = DEFAULT_TOLERANCE) -> Dict[str, Any]:
    """The sentinel verdict for one record against the history."""
    metric = record.get("metric")
    platform = record.get("platform")
    value = float(record["value"])
    # percentile-only history entries carry no scalar value to compare
    same_metric = [h for h in history
                   if h.get("metric") == metric
                   and h.get("value") is not None]
    verdict: Dict[str, Any] = {
        "metric": metric,
        "value": value,
        "unit": record.get("unit"),
        "platform": platform,
    }

    if not same_metric:
        verdict.update(
            verdict="NO_BASELINE",
            reason=f"no committed history for metric {metric!r}",
        )
        return verdict

    chip_history = [h for h in same_metric
                    if h.get("platform") not in (None, "cpu")]
    if _is_fallback(record) or (
        platform == "cpu" and chip_history
    ):
        # A fallback (or platform-mismatched) run
        # can NEVER regress or clear a chip baseline — the baseline is
        # stale, which is its own first-class state.
        pick = max if higher_is_better(record) else min
        best = pick(chip_history, key=lambda h: float(h["value"]),
                    default=None) if chip_history else None
        stale_baseline = record.get("best_known_chip_record") or (
            {
                "value": float(best["value"]),
                "platform": best.get("platform"),
                "source": best.get("_source"),
                "measured_utc": best.get("measured_utc")
                or best.get("_measured_utc"),
            } if best else None
        )
        verdict.update(
            verdict="STALE",
            reason=(
                f"record is a {platform or 'non-chip'} fallback run "
                f"({record.get('fallback_reason') or 'platform mismatch'}); "
                "the chip baseline is stale, not regressed — re-measure on "
                "the chip before trusting either number"
            ),
            stale_baseline=stale_baseline,
        )
        # The r04+ situation surfaced as a NUMBER, not prose: every
        # STALE verdict states how long the chip baseline has gone
        # un-re-measured while rounds fall back to CPU.
        age_days = stale_baseline_age_days(stale_baseline)
        if age_days is not None:
            verdict["stale_baseline_age_days"] = round(age_days, 2)
            cause = (
                "this round fell back to CPU"
                if record.get("fallback_reason")
                else f"this round ran on {platform or 'another platform'}"
            )
            verdict["stale_warning"] = (
                f"chip baseline is {age_days:.1f} days old and {cause} "
                "— the committed chip numbers have not been re-measured "
                "since; treat every chip-derived claim as aging"
            )
        return verdict

    # Untagged history (older records without a platform field — the r04
    # bench_models/gram_sweep lines were all chip runs) counts as
    # comparable for accelerator candidates; CPU candidates only ever
    # compare against explicitly-CPU history.
    if platform == "cpu":
        comparable = [h for h in same_metric if h.get("platform") == "cpu"]
    else:
        comparable = [h for h in same_metric
                      if h.get("platform") in (platform, None)]
    if not comparable:
        verdict.update(
            verdict="NO_BASELINE",
            reason=(
                f"history for {metric!r} exists only on other platforms "
                f"({sorted({h.get('platform') for h in same_metric})})"
            ),
        )
        return verdict

    values = [float(h["value"]) for h in comparable]
    center = _median(values)
    band = noise_band(values, tolerance)
    hib = higher_is_better(record)
    floor = center * (1.0 - band)
    ceil = center * (1.0 + band)
    ratio = value / center if center else None
    baseline = {
        "value": center,
        "n_samples": len(values),
        "sources": sorted({h.get("_source") for h in comparable})[:8],
        "platform": platform,
    }
    verdict.update(
        baseline=baseline,
        band={"relative": round(band, 4), "low": floor, "high": ceil},
        ratio=round(ratio, 4) if ratio is not None else None,
        higher_is_better=hib,
    )
    regressed = value < floor if hib else value > ceil
    if regressed:
        verdict.update(
            verdict="REGRESSED",
            reason=(
                f"value {value:g} is {'below' if hib else 'above'} the "
                f"noise band ({floor:g} .. {ceil:g}) around the "
                f"{len(values)}-sample baseline median {center:g}"
            ),
        )
    else:
        verdict.update(
            verdict="PASS",
            reason=(
                f"value {value:g} is within/beyond the noise band "
                f"({floor:g} .. {ceil:g}) of baseline median {center:g}"
            ),
        )
    return verdict


def _combine_verdicts(kinds) -> str:
    """Worst-wins fold over sub-verdicts: a tail regression can never hide
    behind a healthy mean; NO_BASELINE only when nothing was comparable."""
    for kind in ("REGRESSED", "STALE"):
        if kind in kinds:
            return kind
    if "PASS" in kinds:
        return "PASS"
    return "NO_BASELINE"


def judge_percentiles(record: Dict[str, Any],
                      history: List[Dict[str, Any]],
                      tolerance: float = DEFAULT_TOLERANCE
                      ) -> Dict[str, Any]:
    """Per-percentile verdicts for a record carrying p50/p95/p99 fields.

    Each percentile is judged by ``judge`` against the SAME percentile of
    history records for the metric (a p99 only ever compares to p99s);
    the scalar ``value``, when also present, is judged as before. The
    overall verdict is the worst sub-verdict.
    """
    pcts = record_percentiles(record)
    sub: Dict[str, Dict[str, Any]] = {}
    carry = {
        k: record[k]
        for k in ("platform", "fallback_reason", "best_known_chip_record")
        if k in record
    }
    # Latency percentiles are always lower-is-better, even when the
    # record's scalar unit (or its metric NAME) says rows/sec; the
    # pseudo-records carry an explicit direction, immune to the text
    # heuristic.
    pct_unit = record.get("percentile_unit") or "seconds"
    for key, value in pcts.items():
        pseudo = dict(carry)
        pseudo.update(metric=record.get("metric"), value=value,
                      unit=pct_unit, higher_is_better=False)
        pseudo_history = []
        for h in history:
            if h.get("metric") != record.get("metric"):
                continue
            h_pcts = record_percentiles(h)
            if key not in h_pcts:
                continue
            entry = dict(h)
            entry["value"] = h_pcts[key]
            pseudo_history.append(entry)
        sub[key] = judge(pseudo, pseudo_history, tolerance=tolerance)
    verdicts = list(sub.values())
    if record.get("value") is not None:
        scalar = judge(record, [h for h in history
                                if h.get("value") is not None],
                       tolerance=tolerance)
        verdicts.append(scalar)
    else:
        scalar = None
    overall = _combine_verdicts({v["verdict"] for v in verdicts})
    reason_parts = [f"{key}: {v['verdict']}" for key, v in sub.items()]
    if scalar is not None:
        reason_parts.append(f"scalar: {scalar['verdict']}")
    out: Dict[str, Any] = {
        "metric": record.get("metric"),
        "unit": record.get("unit"),
        "platform": record.get("platform"),
        "verdict": overall,
        "percentiles": sub,
        "reason": "; ".join(reason_parts),
    }
    if scalar is not None:
        out["scalar"] = scalar
        out["value"] = record.get("value")
    return out


def judge_record(record: Dict[str, Any], history: List[Dict[str, Any]],
                 tolerance: float = DEFAULT_TOLERANCE) -> Dict[str, Any]:
    """Dispatch: percentile-aware judging when the record carries
    latency-percentile fields, scalar judging otherwise. A record whose
    backend provenance contradicts its declared/required platform is
    judged STALE with ``reason_code: backend_mismatch`` before any
    number comparison — the measurement itself is untrustworthy, and
    the live-side watchdog raises the same condition as the
    ``fit_backend_degraded`` incident."""
    mismatch = backend_mismatch_reason(record)
    if mismatch:
        return {
            "metric": record.get("metric"),
            "value": record.get("value"),
            "unit": record.get("unit"),
            "platform": record.get("platform"),
            "verdict": "STALE",
            "reason_code": "backend_mismatch",
            "incident": "fit_backend_degraded",
            "reason": (
                f"{mismatch} — the number was measured on the wrong "
                "backend; the comparable baseline is stale, not regressed "
                "(live side raises incident fit_backend_degraded)"
            ),
        }
    if record_percentiles(record):
        return judge_percentiles(record, history, tolerance=tolerance)
    return judge(record, history, tolerance=tolerance)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("record", help="record file (or '-' for stdin): an "
                        "emit_record line, BENCH_rN wrapper, or "
                        "BENCH_MEASURED composite")
    parser.add_argument("--history-root", default=REPO,
                        help="repo root holding BENCH_MEASURED*/records/ "
                        "(default: this repo)")
    parser.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                        help="minimum relative noise band (default 0.15)")
    parser.add_argument("--indent", type=int, default=None,
                        help="pretty-print the verdict JSON")
    args = parser.parse_args(argv)

    record = load_candidate(args.record)
    exclude = None if args.record == "-" else args.record
    history = iter_history(args.history_root, exclude=exclude)
    verdict = judge_record(record, history, tolerance=args.tolerance)
    print(json.dumps(verdict, indent=args.indent, default=str))
    return EXIT_CODES[verdict["verdict"]]


if __name__ == "__main__":
    sys.exit(main())
