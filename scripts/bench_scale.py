"""Quadratic-algorithm scale demonstration: DBSCAN + UMAP at 200k×64.

VERDICT r3 task #5 (carried from r2 #6): prove the tiled kernels handle
200k rows on one chip without OOM — the dense n×n formulation would need
n²·4B = 160 GB of HBM at this size; the tiled sweeps keep a block×n panel
(block 4096 → 3.3 GB) plus O(n) state resident. Prints one JSON line per
model: rows, wall-clock, peak device bytes (from PJRT memory_stats when
the backend exposes them), and the block envelope the peak must stay
inside. Asserts no-OOM by construction (completing is the proof) and,
when memory stats exist, that peak stays under the envelope.

On the CPU the row count and epoch/sweep budgets shrink (the point is the
chip run; the CPU only proves the code path end-to-end, and its record is
stamped ``platform: cpu``).
"""

from __future__ import annotations

import json  # noqa: F401 - kept for ad-hoc debugging
import os
import time

from bench_common import emit_record

import numpy as np

# the ONE watermark reader all benches share (obs/memory.py); the ad-hoc
# device.memory_stats() parsing that used to live here is retired
from spark_rapids_ml_tpu.obs.memory import peak_bytes_in_use as _peak_bytes


def main() -> None:
    import jax

    from spark_rapids_ml_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    platform = device.platform
    on_chip = platform not in ("cpu",)

    rows = int(os.environ.get("BSCALE_ROWS", 200_000 if on_chip else 40_000))
    cols = int(os.environ.get("BSCALE_COLS", 64))
    block = int(os.environ.get("BSCALE_BLOCK", 4096))
    umap_epochs = int(os.environ.get("BSCALE_UMAP_EPOCHS",
                                     50 if on_chip else 5))

    # well-separated blobs: DBSCAN's label-propagation sweep count stays
    # bounded by cluster diameter, and UMAP has real structure to embed
    rng = np.random.default_rng(0)
    n_blobs = 16
    centers = rng.normal(scale=12.0, size=(n_blobs, cols))
    assign = rng.integers(0, n_blobs, size=rows)
    x = centers[assign] + rng.normal(size=(rows, cols))

    # panel envelope: one (block, rows) f32 panel + x + O(rows) state,
    # with 4x headroom for XLA temporaries/donation copies
    envelope_bytes = 4 * (block * rows * 4 + x.nbytes + 64 * rows)

    from spark_rapids_ml_tpu.models.dbscan import DBSCAN
    from spark_rapids_ml_tpu.models.umap import UMAP

    records = []

    # eps: in 64 dims intra-blob pairwise distances concentrate at
    # √(2·64) ≈ 11.3 ± ~1 (σ=1 blobs), inter-blob centers ~136 apart —
    # eps=13 densely connects blobs and never bridges them
    t0 = time.perf_counter()
    db = DBSCAN().setEps(13.0).setMinPts(5).setBlockRows(block).fit(x)
    db_seconds = time.perf_counter() - t0
    n_clusters = int(db.n_clusters_)
    peak = _peak_bytes(device)
    rec = {
        "metric": f"DBSCAN.fit seconds ({rows}x{cols}, tiled block={block})",
        "value": round(db_seconds, 2),
        "unit": "seconds",
        "rows": rows,
        "platform": platform,
        "device_kind": str(getattr(device, "device_kind", platform)),
        "n_clusters": n_clusters,
        "rows_per_sec": round(rows / db_seconds, 1),
        "peak_device_bytes": peak,
        "envelope_bytes": envelope_bytes,
        "dense_equivalent_bytes": rows * rows * 4,
        "fit_timings": db.fit_timings_,
    }
    if peak is not None:
        assert peak < envelope_bytes, (
            f"peak {peak} exceeds block envelope {envelope_bytes}"
        )
        rec["within_envelope"] = True
    # widely-separated blobs: (nearly) every blob must resolve
    assert n_clusters >= n_blobs // 2, f"degenerate clustering: {n_clusters}"
    records.append(rec)
    emit_record(rec)

    t0 = time.perf_counter()
    um = (
        UMAP().setNNeighbors(15).setNEpochs(umap_epochs)
        .setBlockRows(block).fit(x)
    )
    um_seconds = time.perf_counter() - t0
    peak = _peak_bytes(device)
    emb = np.asarray(um.embedding_)
    assert np.isfinite(emb).all()
    # blob structure must survive the embedding: average inter-centroid
    # distance well above average intra-blob spread
    cent = np.stack([emb[assign == b].mean(axis=0) for b in range(n_blobs)])
    intra = float(np.mean([
        np.linalg.norm(emb[assign == b] - cent[b], axis=1).mean()
        for b in range(n_blobs)
    ]))
    inter = float(np.linalg.norm(
        cent[:, None, :] - cent[None, :, :], axis=-1
    )[np.triu_indices(n_blobs, 1)].mean())
    rec = {
        "metric": f"UMAP.fit seconds ({rows}x{cols}, tiled block={block}, "
                  f"epochs={umap_epochs})",
        "value": round(um_seconds, 2),
        "unit": "seconds",
        "rows": rows,
        "platform": platform,
        "device_kind": str(getattr(device, "device_kind", platform)),
        "rows_per_sec": round(rows / um_seconds, 1),
        "peak_device_bytes": peak,
        "envelope_bytes": envelope_bytes,
        "dense_equivalent_bytes": rows * rows * 4,
        "separation_ratio": round(inter / max(intra, 1e-9), 2),
        "fit_timings": um.fit_timings_,
    }
    if peak is not None:
        assert peak < envelope_bytes, (
            f"peak {peak} exceeds block envelope {envelope_bytes}"
        )
        rec["within_envelope"] = True
    # structure floor: blob centroids must already be pulling apart (the
    # recorded separation_ratio carries the full-budget evidence; the
    # reduced-epoch CPU smoke only proves direction)
    assert inter > 1.15 * intra, (
        f"blob structure lost: inter {inter:.2f} vs intra {intra:.2f}"
    )
    records.append(rec)
    emit_record(rec)


if __name__ == "__main__":
    main()
