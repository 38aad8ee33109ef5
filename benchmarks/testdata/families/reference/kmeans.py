"""Plain Lloyd reference (a test fixture) and the comparison that decides
``correct`` for a KMeans fitted over a re-iterable source.

Imports nothing of the program. The configuration's guarantee is Lloyd's
fixed point: every centre the mean of the rows nearest to it. So the
reference does not fit a model of its own (that would depend on the
initialisation): from each model the timed fits returned (``model_of``:
the centres) it runs ONE plain Lloyd step over the same host chunks —
squared distances in ``jax.numpy`` float32 at ``highest`` matmul
precision, the nearest centre, the per-cluster sums of the rows in
float64 on the host — and compares by one number:

- ``lloyd_shift``: max over centres of |new centre - centre| (an empty
  cluster keeps its centre) over the RMS distance of a row to its nearest
  centre (the clusters' spread): how far the program's answer stands from
  a fixed point, in units of the clusters' own size.

``FAULTS`` plant a broken timed path for the tests: the answer altered
where the model is made, and every accumulate step leaving half of its
batch out.
"""

from __future__ import annotations

import numpy as np

REF_BLOCK_ROWS = 1 << 16


def model_of(fitted) -> dict:
    return {"centers": np.asarray(fitted.cluster_centers)}


def reference(chunks, device=None) -> dict:
    """Nothing to compute before a model is there: the rows and the
    device the Lloyd step runs on."""
    return {"chunks": chunks, "device": device}


def lloyd_step(centers: np.ndarray, chunks, device=None) -> tuple:
    """(new centres, RMS distance of a row to its nearest centre) of one
    Lloyd step from ``centers``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def assign(x, c):
        d = (jnp.sum(x * x, 1)[:, None] + jnp.sum(c * c, 1)[None, :]
             - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST))
        return jnp.argmin(d, 1), jnp.maximum(jnp.min(d, 1), 0.0)

    k, n = centers.shape
    c_dev = jax.device_put(centers.astype(np.float32), device)
    sums, counts, sq, rows = np.zeros((k, n)), np.zeros(k), 0.0, 0
    for x in chunks:
        for start in range(0, x.shape[0], REF_BLOCK_ROWS):
            block = x[start:start + REF_BLOCK_ROWS]
            nearest, dist = assign(jax.device_put(block, device), c_dev)
            nearest = np.asarray(nearest)
            np.add.at(sums, nearest, block.astype(np.float64))
            counts += np.bincount(nearest, minlength=k)
            sq += float(np.sum(np.asarray(dist, dtype=np.float64)))
            rows += block.shape[0]
    new = np.where(counts[:, None] > 0,
                   sums / np.maximum(counts, 1)[:, None], centers)
    return new, float(np.sqrt(sq / rows))


def gaps(model: dict, ref: dict) -> dict:
    centers = np.asarray(model["centers"], dtype=np.float64)
    new, spread = lloyd_step(centers, ref["chunks"], ref["device"])
    return {"lloyd_shift": float(
        np.max(np.linalg.norm(new - centers, axis=1)) / spread)}


def compare(models: list, ref: dict, limits: dict):
    """Worst gap over the window's distinct models beside its limit:
    (correct, {name: {"value": v, "limit": l}}). A NaN, or a name without
    a limit, is not correct."""
    worst: dict = {}
    seen: list = []
    for model in models:
        # fits of one window see the same rows and the same seed
        if any(np.array_equal(model["centers"], o["centers"]) for o in seen):
            continue
        seen.append(model)
        for name, value in gaps(model, ref).items():
            prev = worst.get(name)
            if prev is None or (prev == prev and not value <= prev):
                worst[name] = value  # a NaN, once in, stays
    compared = {name: {"value": value, "limit": limits.get(name)}
                for name, value in worst.items()}
    correct = bool(models) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    return correct, compared


# -- planted faults (the tests') ----------------------------------------------


def _half_of(batch, mask):
    """The batch with the second half of its valid rows (the first ones;
    the padding follows them) replaced by the first half: half of the
    rows left out, the rest counted twice, the count unchanged."""
    valid = batch.shape[0] if mask is None else int(np.sum(np.asarray(mask)))
    half = valid // 2
    return batch.at[half:2 * half].set(batch[:half])


def break_centers_altered(monkeypatch):
    """The answer's centres altered where the model is made: the first
    coordinate of every centre moved by 1e-3."""
    from spark_rapids_ml_tpu.models import kmeans

    real = kmeans.KMeansModel.__init__

    def init(self, *args, **kwargs):
        centers = np.array(kwargs["cluster_centers"])
        centers[:, 0] += 1e-3
        kwargs["cluster_centers"] = centers
        real(self, *args, **kwargs)

    monkeypatch.setattr(kmeans.KMeansModel, "__init__", init)


def break_half_batch(monkeypatch):
    """Every accumulate step leaves half of its batch out and counts the
    rest twice."""
    from spark_rapids_ml_tpu.ops import kmeans_kernel

    real = kmeans_kernel.update_cluster_stats

    def step(carry, centers, batch, mask=None, *args, **kwargs):
        return real(carry, centers, _half_of(batch, mask), mask,
                    *args, **kwargs)

    monkeypatch.setattr(kmeans_kernel, "update_cluster_stats", step)


FAULTS = {
    "centers_altered": break_centers_altered,
    "half_batch": break_half_batch,
}
