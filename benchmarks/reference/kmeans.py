"""Plain Lloyd reference and the comparison that decides ``correct`` for a
KMeans fitted on the streamed loop (``configs/kmeans-3000-k1000.json``).

Imports nothing of the program. The configuration fixes Lloyd's steps, not
their end: ``tol`` is below anything a float32 centre can move, so a fit
runs ``maxIter`` steps from ``initMode`` "random"'s k rows. What the model
returns (``model_of``) is its final centres, the initial centres it drew
(``initial_centers_``), its cost under the final centres
(``training_cost_``) and its steps (``n_iter_``). A plain Lloyd step here:
every row put on the chip once, in blocks; squared distances
``|x|^2 + |c|^2 - 2 x.c`` in ``jax.numpy`` float32 at ``highest`` matmul
precision; the nearest centre; each block's per-cluster sums (a one-hot
product at ``highest``) and counts fetched and added in float64 on the
host; an empty cluster keeps its centre. Four numbers over the RMS
distance of a row to the program's final centre (the clusters' spread),
and a count:

- ``lloyd_shift``: the MEDIAN over centres of |one reference step from the
  program's final centres - those centres|. Thirty steps from random rows
  leave a share of the clusters still moving (a cluster that drew two
  initial rows splits its rows between them slowly: 17-23 % of the
  centres at the configuration's size), so one more step moves a centre
  there by up to ~1e-2 of the spread whatever the arithmetic;
  where a cluster has stopped, a float32 step reproduces the program's
  centre to its rounding, and a step whose products ran at one bfloat16
  pass stands off by its sums' rounding. The median reads the clusters
  that stopped.
- ``lloyd_shift_p95``: the 95th percentile of the same shifts: a fault in
  a twentieth of the centres or more (a tile of the k axis) that moves
  them further than the still-moving clusters go. The median cannot see
  a fault in fewer than half of the centres, nor the first quartile below
  in fewer than three quarters.
- ``cost_gap``: |``training_cost_`` - the reference's cost under the
  program's final centres| / the latter: the program's cost pass.
- ``centre_gap``: the FIRST QUARTILE over centres of |c - c_ref|, where
  ``c_ref`` are ``n_iter_`` reference steps from the model's own initial
  centres: the whole trajectory. Lloyd from random rows is chaotic at a
  rounding's scale — a row whose two nearest centres tie to the last bit
  goes either way, and the clusters around it part from there — so a
  float32 program and a float32 reference that sums in another order end
  up ~1e-2 of the spread apart in up to half of the clusters (third
  quartile 1.1e-2 - 1.5e-2 on nine seeds of ten at the configuration's
  size; PERF.md section 2); the first quartile reads the clusters no tie
  reached, and what reads there is a fault of the whole path.
- ``initial_not_rows``: how many of the k initial centres are not k
  distinct rows of the data (``initMode`` "random"'s guarantee): a centre
  that is no row's bits in the rows' dtype, or a row drawn twice. The
  other numbers start the reference where the model says it started, so
  only this one holds the draw itself.

``FAULTS`` plant a broken timed path for the tests: the answer altered
where the model is made, every step leaving half of its batch out, the
last tenth of the centres' sums (a tile of the k axis) taking half of each
batch, the draw taking row 0 of every batch for every centre, and the
step's two products at one bfloat16 MXU pass (the precision below the
configuration's float32: the control).
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

REF_BLOCK_ROWS = 1 << 16


def model_of(fitted) -> dict:
    return {"centers": np.asarray(fitted.cluster_centers),
            "initial": np.asarray(fitted.initial_centers_),
            "cost": float(fitted.training_cost_),
            "n_iter": int(fitted.n_iter_)}


def reference(chunks, device=None) -> dict:
    """Nothing to compute before a model is there: the rows, put on
    ``device`` on first use, and kept there for every step."""
    return {"chunks": chunks, "device": device, "blocks": None}


def _blocks(ref: dict) -> list:
    import jax

    if ref["blocks"] is None:
        ref["blocks"] = [jax.device_put(x[start:start + REF_BLOCK_ROWS],
                                        ref["device"])
                         for x in ref["chunks"]
                         for start in range(0, x.shape[0], REF_BLOCK_ROWS)]
    return ref["blocks"]


@lru_cache(maxsize=None)
def _block_step_fn():
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    @jax.jit
    def block_step(x, c):
        d = (jnp.sum(x * x, 1)[:, None] + jnp.sum(c * c, 1)[None, :]
             - 2.0 * jnp.matmul(x, c.T, precision=highest))
        onehot = jax.nn.one_hot(jnp.argmin(d, 1), c.shape[0],
                                dtype=jnp.float32)
        return (jnp.matmul(onehot.T, x, precision=highest),
                jnp.sum(onehot, 0), jnp.sum(jnp.maximum(jnp.min(d, 1), 0.0)))

    return block_step


def lloyd_step(centers: np.ndarray, ref: dict) -> tuple:
    """(new centres, cost under ``centers``) of one plain Lloyd step."""
    import jax

    block_step = _block_step_fn()
    k, n = centers.shape
    c_dev = jax.device_put(centers.astype(np.float32), ref["device"])
    sums, counts, cost = np.zeros((k, n)), np.zeros(k), 0.0
    for block in _blocks(ref):
        s, c, co = block_step(block, c_dev)
        sums += np.asarray(s, dtype=np.float64)
        counts += np.asarray(c, dtype=np.float64)
        cost += float(co)
    new = np.where(counts[:, None] > 0,
                   sums / np.maximum(counts, 1)[:, None], centers)
    return new, cost


def _head_keys(rows: np.ndarray) -> np.ndarray:
    """A 64-bit key of each row's first four values' bits: a row that may
    be a centre, to be compared whole."""
    head = np.ascontiguousarray(rows[:, :4]).view(np.uint8)
    words = np.pad(head, ((0, 0), (0, -head.shape[1] % 8))).view(np.uint64)
    key = np.zeros(rows.shape[0], dtype=np.uint64)
    for word in words.T:
        key = key * np.uint64(0x100000001B3) ^ word
    return key


def initial_not_rows(initial: np.ndarray, chunks) -> int:
    """k less the number of distinct rows of ``chunks`` that the initial
    centres are, bit for bit in the rows' dtype: 0 where the k centres are
    k distinct rows."""
    initial = np.asarray(initial)
    cast = initial.astype(chunks[0].dtype)
    exact = np.all(cast == initial, axis=1)  # a NaN is no row
    keys = _head_keys(cast)
    found = set()
    for x in chunks:
        row_keys = _head_keys(x)
        for r in np.flatnonzero(np.isin(row_keys, keys[exact])):
            found.update(cast[j].tobytes() for j in np.flatnonzero(
                exact & (keys == row_keys[r]))
                if np.array_equal(cast[j], x[r]))
    return initial.shape[0] - len(found)


def gaps(model: dict, ref: dict) -> dict:
    centers = np.asarray(model["centers"], dtype=np.float64)
    rows = sum(x.shape[0] for x in ref["chunks"])
    one, cost = lloyd_step(centers, ref)
    spread = np.sqrt(cost / rows)
    c_ref = np.asarray(model["initial"], dtype=np.float64)
    for _ in range(model["n_iter"]):
        c_ref, _ = lloyd_step(c_ref, ref)
    shift = np.linalg.norm(one - centers, axis=1) / spread
    return {
        "lloyd_shift": float(np.median(shift)),
        "lloyd_shift_p95": float(np.quantile(shift, 0.95)),
        "cost_gap": abs(model["cost"] - cost) / cost,
        "centre_gap": float(np.quantile(
            np.linalg.norm(centers - c_ref, axis=1), 0.25) / spread),
        "initial_not_rows": initial_not_rows(model["initial"], ref["chunks"]),
    }


def compare(models: list, ref: dict, limits: dict):
    """Worst gap over the window's distinct models beside its limit:
    (correct, {name: {"value": v, "limit": l}}). A NaN, or a name without
    a limit, is not correct."""
    worst: dict = {}
    seen: list = []
    for model in models:
        # fits of one window see the same rows and the same seed
        if any(np.array_equal(model["centers"], o["centers"])
               and np.array_equal(model["initial"], o["initial"])
               for o in seen):
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


# -- planted faults (the tests'; the control) ---------------------------------


def _half_of(batch, mask):
    """The batch with the second half of its valid rows (the first ones;
    the padding follows them) replaced by the first half: half of the
    rows left out, the rest counted twice, the count unchanged."""
    valid = batch.shape[0] if mask is None else int(np.sum(np.asarray(mask)))
    half = valid // 2
    return batch.at[half:2 * half].set(batch[:half])


def break_centers_altered(monkeypatch):
    """The answer's centres altered where the model is made: the first
    coordinate of every centre moved by a tenth of the centres' RMS
    distance from their mean."""
    from spark_rapids_ml_tpu.models import kmeans

    real = kmeans.KMeansModel.__init__

    def init(self, *args, **kwargs):
        centers = np.array(kwargs["cluster_centers"])
        spread = np.sqrt(np.mean(np.sum(
            (centers - centers.mean(axis=0)) ** 2, axis=1)))
        centers[:, 0] += 0.1 * spread
        kwargs["cluster_centers"] = centers
        real(self, *args, **kwargs)

    monkeypatch.setattr(kmeans.KMeansModel, "__init__", init)


def break_half_batch(monkeypatch):
    """Every step leaves half of its batch out and counts the rest
    twice."""
    from spark_rapids_ml_tpu.ops import kmeans_kernel

    real = kmeans_kernel.update_cluster_stats

    def step(carry, centers, batch, mask=None, *args, **kwargs):
        return real(carry, centers, _half_of(batch, mask), mask,
                    *args, **kwargs)

    monkeypatch.setattr(kmeans_kernel, "update_cluster_stats", step)


def break_tile_sums(monkeypatch):
    """A tile of the k axis wrong: the last tenth of the centres (at least
    one) take half of each batch's sums into their own."""
    from spark_rapids_ml_tpu.ops import kmeans_kernel

    real = kmeans_kernel.update_cluster_stats

    def step(carry, centers, batch, mask=None, *args, **kwargs):
        k = centers.shape[0]
        start = k - max(1, k // 10)
        before = carry[0][start:] + 0  # a copy: the carry is donated
        sums, counts, cost = real(carry, centers, batch, mask, *args,
                                  **kwargs)
        tile = before + 0.5 * (sums[start:] - before)
        return sums.at[start:].set(tile), counts, cost

    monkeypatch.setattr(kmeans_kernel, "update_cluster_stats", step)


def break_init_row_zero(monkeypatch):
    """``initMode`` "random"'s draw broken: every centre takes row 0 of
    the batch its row was drawn from — the host copy and the gather from a
    batch kept on the chip alike — so the k centres repeat a few rows."""
    from spark_rapids_ml_tpu.models import kmeans
    from spark_rapids_ml_tpu.ops import kmeans_kernel

    real_see = kmeans._RandomRows.see
    real_take = kmeans_kernel.take_rows

    def see(self, batch, mask, kept=-1):
        real_see(self, np.broadcast_to(batch[:1], batch.shape), mask, kept)

    def take_rows(centers, batch, rows, take):
        return real_take(centers, batch, rows * 0, take)

    monkeypatch.setattr(kmeans._RandomRows, "see", see)
    monkeypatch.setattr(kmeans_kernel, "take_rows", take_rows)


def break_step_bf16(monkeypatch):
    """The control: the step's two products — the distances' cross term
    and the per-cluster sums — at one bfloat16 MXU pass (operands rounded
    to bfloat16, products summed in float32), the precision below the
    configuration's float32 at ``highest``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import kmeans_kernel

    def one_pass(a, b, dims):
        return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (dims, ((), ())),
                               preferred_element_type=jnp.float32)

    @partial(jax.jit, donate_argnums=(0,))
    def step(carry, centers, batch, mask=None):
        sums, counts, cost = carry
        x = batch.astype(sums.dtype)
        valid = (jnp.ones(x.shape[0], x.dtype) if mask is None
                 else mask.astype(x.dtype))
        d = jnp.maximum(jnp.sum(x * x, 1)[:, None]
                        + jnp.sum(centers * centers, 1)[None, :]
                        - 2.0 * one_pass(x, centers, ((1,), (1,))), 0.0)
        onehot = jax.nn.one_hot(jnp.argmin(d, 1), centers.shape[0],
                                dtype=x.dtype) * valid[:, None]
        return (sums + one_pass(onehot, x, ((0,), (0,))).astype(sums.dtype),
                counts + jnp.sum(onehot, 0).astype(counts.dtype),
                cost + jnp.sum(jnp.min(d, 1) * valid))

    monkeypatch.setattr(kmeans_kernel, "update_cluster_stats", step)


FAULTS = {
    "centers_altered": break_centers_altered,
    "half_batch": break_half_batch,
    "init_row_zero": break_init_row_zero,
    "step_bf16": break_step_bf16,
    "tile_sums": break_tile_sums,
}
