"""Seeded rows for the fit cells — the benchmark's one data generator.

A configuration's ``rows`` group holds the recipe; this file turns it and
``--seed`` into host chunks. Rows are drawn on the device in blocks of a
few hundred MiB (so that set-up never holds more on the chip than a fit
does) and fetched into host buffers once. The same seed gives the same
rows; every seed gives the same sizes.

Recipe: ``x[r, j] = s_r * g[r, j] * (1 + j) ** -spectrum_power + mean[j]``
with g standard normal, ``mean = mean_scale * normal`` and a per-row
scale ``s_r = exp(row_scale_sigma * normal)``. The column variances decay
as a power law so the leading components are separated (what
``chip_smoke.py`` uses); the non-zero mean makes centering do work; the
log-normal row scale gives the rows the unequal norms real feature rows
have, and it is what makes a Gram in one bfloat16 pass visibly worse than
the float32 one: with equal rows its rounding averages away over half a
million of them (PERF.md, Findings PR 25).

Two optional groups of the recipe dress those rows for other estimator
families; both are drawn on the device in the same blocks, from keys of
their own (so the rows above stay what they are), and fetched once:

- ``"clusters": {"k": K, "spread": s, "skew": a}``: row r becomes
  ``centre[c_r] + s * x[r]`` with K seeded standard-normal centres and
  ``c_r`` drawn Zipf(a) over the K (P(c) ∝ (1 + c) ** -a; a = 0 uniform).
- ``"labels": {"kind": "linear" | "binary", "noise": σ}``: with a seeded
  standard-normal β, ``y = x β + σ ε`` (linear) or ``1[x β + σ ε > 0]``
  (binary), ε standard normal; every chunk is then an ``(X, y)`` tuple,
  the form the supervised estimators' streamed fits take. ``n_features``
  counts X's columns only.

With neither group a chunk is the same array, byte for byte, as before the
groups existed (``test_benchmark.py`` pins a hash of each configuration's
rows).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

BLOCKS_PER_CHUNK = 8
FETCH_THREADS = 3


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number below 2**62: the low
    31 bits seed the key and the rest is folded in, so seeds past 2**31
    neither overflow nor collide with their low halves."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _block_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("rows", "n", "power", "mean_scale",
                                       "row_sigma"))
    def block(key, index, *, rows, n, power, mean_scale, row_sigma):
        k_mean, k_data = jax.random.split(key)
        mean = mean_scale * jax.random.normal(k_mean, (n,), jnp.float32)
        k_g, k_s = jax.random.split(jax.random.fold_in(k_data, index))
        g = jax.random.normal(k_g, (rows, n), jnp.float32)
        s = jnp.exp(row_sigma * jax.random.normal(k_s, (rows, 1),
                                                  jnp.float32))
        col = (1.0 + jnp.arange(n, dtype=jnp.float32)) ** -power
        return g * s * col[None, :] + mean[None, :]

    return block


# fold_in constants of the groups' keys: apart from the rows' own split
CLUSTERS_KEY, LABELS_KEY = 0x636C7573, 0x6C61626C


def _dress_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("k", "spread", "skew", "kind",
                                       "noise"))
    def dress(key, index, x, *, k, spread, skew, kind, noise):
        rows, n = x.shape
        y = None
        if k:
            k_centres, k_of = jax.random.split(
                jax.random.fold_in(key, CLUSTERS_KEY))
            centres = jax.random.normal(k_centres, (k, n), jnp.float32)
            logits = -skew * jnp.log1p(jnp.arange(k, dtype=jnp.float32))
            c = jax.random.categorical(jax.random.fold_in(k_of, index),
                                       logits, shape=(rows,))
            x = centres[c] + spread * x
        if kind:
            k_beta, k_eps = jax.random.split(
                jax.random.fold_in(key, LABELS_KEY))
            beta = jax.random.normal(k_beta, (n,), jnp.float32)
            eps = jax.random.normal(jax.random.fold_in(k_eps, index),
                                    (rows,), jnp.float32)
            y = jnp.matmul(x, beta, precision=jax.lax.Precision.HIGHEST) \
                + noise * eps
            if kind == "binary":
                y = (y > 0).astype(jnp.float32)
        return x, y

    return dress


def make_chunks(seed: int, n_features: int, chunk_rows: int, n_chunks: int,
                recipe: dict, device=None) -> list:
    """``n_chunks`` C-contiguous float32 arrays of (chunk_rows, n_features),
    or ``(X, y)`` tuples of them and (chunk_rows,) labels where the recipe
    has ``labels``."""
    import jax

    block_rows = chunk_rows // BLOCKS_PER_CHUNK
    if block_rows * BLOCKS_PER_CHUNK != chunk_rows:
        raise ValueError(f"chunk_rows={chunk_rows} is not a multiple of "
                         f"{BLOCKS_PER_CHUNK}")
    key = jax.device_put(seed_key(seed), device)
    block = _block_fn()
    draw = partial(block, key, rows=block_rows, n=n_features,
                   power=float(recipe["spectrum_power"]),
                   mean_scale=float(recipe["mean_scale"]),
                   row_sigma=float(recipe["row_scale_sigma"]))
    clusters, labels = recipe.get("clusters"), recipe.get("labels")
    if clusters or labels:
        if labels and labels["kind"] not in ("linear", "binary"):
            raise ValueError(f"unknown labels kind {labels['kind']!r}")
        dress = partial(
            _dress_fn(), key,
            k=int(clusters["k"]) if clusters else 0,
            spread=float(clusters["spread"]) if clusters else 1.0,
            skew=float(clusters.get("skew", 0.0)) if clusters else 0.0,
            kind=labels["kind"] if labels else "",
            noise=float(labels["noise"]) if labels else 0.0)
        plain = draw

        def draw(i):
            return dress(i, plain(i))

    chunks = [np.empty((chunk_rows, n_features), dtype=np.float32)
              for _ in range(n_chunks)]
    ys = [np.empty(chunk_rows, dtype=np.float32)
          for _ in range(n_chunks)] if labels else None

    def fetch(i: int) -> None:
        c, b = divmod(i, BLOCKS_PER_CHUNK)
        part = slice(b * block_rows, (b + 1) * block_rows)
        drawn = draw(np.int32(i))
        if isinstance(drawn, tuple):
            drawn, y = drawn
            if ys is not None:
                ys[c][part] = np.asarray(y)
        chunks[c][part] = np.asarray(drawn)

    # a few threads, so that one block's device-to-host copy overlaps the
    # next one's draw and the host's first touch of the chunk's pages; at
    # most FETCH_THREADS blocks are alive on the chip
    with ThreadPoolExecutor(FETCH_THREADS) as pool:
        list(pool.map(fetch, range(n_chunks * BLOCKS_PER_CHUNK)))
    return chunks if ys is None else list(zip(chunks, ys))
