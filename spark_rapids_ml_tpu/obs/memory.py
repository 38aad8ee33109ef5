"""Device-memory watermark telemetry (PJRT ``memory_stats``).

One shared reader for what ``scripts/bench_scale.py`` used to hand-roll:
per-device ``memory_stats()`` (PJRT maintains ``peak_bytes_in_use`` as a
true high-watermark, so an end-of-phase read IS the watermark — no sampling
thread needed), folded uniformly into ``FitReport.peak_device_bytes`` /
``FitReport.memory``, the metrics registry, and every bench record.

Backends without PJRT stats (CPU included) report the process RSS peak
(``getrusage ru_maxrss``) instead, with ``source: "host_rss"`` so a host
number is never mistaken for an HBM number.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def device_memory_stats(device) -> Optional[Dict[str, Any]]:
    """``device.memory_stats()`` guarded: None when the backend has no
    stats (CPU) or the call fails (a backend fault must not break telemetry)."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return dict(stats)


def peak_bytes_in_use(device) -> Optional[int]:
    """One device's peak bytes in use (falls back to current bytes in use
    on runtimes that track no peak), or None without stats."""
    stats = device_memory_stats(device)
    if stats is None:
        return None
    peak = int(stats.get("peak_bytes_in_use",
                         stats.get("bytes_in_use", 0)))
    return peak or None


def host_peak_rss_bytes() -> Optional[int]:
    """Process-lifetime RSS high-watermark (ru_maxrss is KiB on Linux,
    bytes on macOS)."""
    try:
        import resource
        import sys

        scale = 1 if sys.platform == "darwin" else 1024
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * scale
    except Exception:
        return None


def host_current_rss_bytes() -> Optional[int]:
    """CURRENT process RSS (``/proc/self/statm`` resident pages × page
    size) — unlike ``ru_maxrss`` this goes DOWN when memory is freed, so
    a gauge fed from it shows a trend, not a high-watermark. None where
    /proc is unavailable (macOS)."""
    try:
        import resource

        with open("/proc/self/statm") as f:
            resident_pages = int(f.read().split()[1])
        return resident_pages * resource.getpagesize()
    except Exception:
        return None


def memory_watermarks(devices=None) -> Dict[str, Any]:
    """The uniform watermark snapshot every report/bench embeds.

    Returns ``{"source": "pjrt"|"host_rss"|"none", "peak_bytes": int|None,
    "host_peak_rss_bytes": int|None, "per_device": [...]}`` — ``peak_bytes``
    is the max PJRT per-device watermark when any device exposes stats,
    else the host RSS peak (so a CPU run still carries a concrete number,
    visibly host-sourced).
    """
    per_device: List[Dict[str, Any]] = []
    if devices is None:
        try:
            import jax

            devices = jax.devices()
        except Exception:
            devices = []
    device_peaks = []
    for d in devices:
        stats = device_memory_stats(d)
        entry: Dict[str, Any] = {"device": str(d)}
        if stats is not None:
            peak = int(stats.get("peak_bytes_in_use",
                                 stats.get("bytes_in_use", 0)))
            entry["peak_bytes_in_use"] = peak
            entry["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            if "bytes_limit" in stats:
                entry["bytes_limit"] = int(stats["bytes_limit"])
            device_peaks.append(peak)
        per_device.append(entry)
    rss = host_peak_rss_bytes()
    if device_peaks:
        source = "pjrt"
        peak: Optional[int] = max(device_peaks)
    elif rss is not None:
        source = "host_rss"
        peak = rss
    else:
        source = "none"
        peak = None
    return {
        "source": source,
        "peak_bytes": peak,
        "host_peak_rss_bytes": rss,
        "per_device": per_device,
    }


def record_memory_metrics(watermarks: Optional[Dict[str, Any]] = None) -> None:
    """Export a watermark snapshot into the process metrics registry
    (``sparkml_device_peak_bytes{device=}`` + host RSS gauge)."""
    try:
        from spark_rapids_ml_tpu.obs.metrics import get_registry

        wm = watermarks if watermarks is not None else memory_watermarks()
        reg = get_registry()
        for entry in wm.get("per_device", ()):
            if "peak_bytes_in_use" in entry:
                reg.gauge(
                    "sparkml_device_peak_bytes",
                    "per-device peak bytes in use (PJRT watermark)",
                    ("device",),
                ).set(entry["peak_bytes_in_use"], device=entry["device"])
        if wm.get("host_peak_rss_bytes") is not None:
            reg.gauge(
                "sparkml_host_peak_rss_bytes",
                "process RSS high-watermark",
            ).set(wm["host_peak_rss_bytes"])
    except Exception:
        pass  # telemetry must never break the caller
