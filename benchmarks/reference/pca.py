"""Plain PCA reference and the comparison that decides ``correct``.

Imports nothing of the program. The reference is the textbook two-pass
fit on the same host chunks: column means, then the centred Gram
``(X - mean)^T (X - mean) / (N - 1)``, in plain ``jax.numpy`` at
``highest`` matmul precision, in blocks of rows so that it fits beside
nothing else, with the block results summed by an error-free two-sum (the
chip has no float64). The eigenvalues of that covariance come from NumPy
in float64 on the host.

What is compared is the model the timed fits returned — ``pc`` (n, k),
``explained_variance`` (k,) and ``mean`` (n,) — against that covariance
``C``, its trace ``T`` and its eigenvalues, by three numbers. With
``A = pc^T C pc`` and ``theta = diag(A)``:

- ``mean_gap``: max |mean - mean_ref| over max |mean_ref|.
- ``ritz_gap``: max_j |explained_variance_j * T / theta_j - 1|. Any
  Rayleigh-Ritz solve returns eigenvalues that ARE its vectors' Rayleigh
  quotients on the covariance it was given, so this reads the error of
  the program's covariance (rows dropped, masks, Gram precision) and of
  the explained variances, whatever the solve's convergence.
  A zero or non-finite column makes it infinite or NaN, which fails.
- ``miss_gap``: 1 - trace(A) / (sum of the k largest eigenvalues of C):
  the share of the best k-subspace's variance that the returned subspace
  misses. This is the one that reads the solve's convergence.

(The off-diagonal of ``A`` was tried as a fourth number and dropped: the
float32 solve's own loss of orthogonality, ~1e-5, times the ratio of a
large to a small eigenvalue reads as much as the control does.)

``lower_precision_model`` is the control: this reference put in the
program's place and computed in bfloat16, the step below the float32 the
configurations state — rows and centred rows rounded to bfloat16 before
they are multiplied (products still summed in float32, as the MXU does)
and the column sums kept in a bfloat16 accumulator.

``model_of`` is what the harness takes from a fitted model, inside every
timed fit: the three host arrays compared. ``FAULTS`` are the breaks of
the timed path that the comparison has to see, planted by the benchmark's
tests (``test_benchmark.py``) with pytest's ``monkeypatch``: only they
reach into the program, and only in a test.
"""

from __future__ import annotations

import numpy as np

REF_BLOCK_BYTES = 256 << 20


def _block_rows(n_features: int, chunk_rows: int) -> int:
    rows = max(1, REF_BLOCK_BYTES // (4 * n_features))
    while chunk_rows % rows:
        rows -= 1
    return rows


def _two_sum(hi, lo, v):
    t = hi + v
    bb = t - hi
    return t, lo + ((hi - (t - bb)) + (v - bb))


def moments(chunks, device=None, round_to=None):
    """(mean[n] f64, covariance[n, n] f64, rows) of the stacked chunks.

    ``round_to`` (a dtype name) rounds each row block, each centred block
    and the running column sum to that type: the lower-precision
    control."""
    import jax
    import jax.numpy as jnp

    n = chunks[0].shape[1]
    total = sum(c.shape[0] for c in chunks)

    def rounded(x):
        return x if round_to is None else x.astype(round_to).astype(x.dtype)

    @jax.jit
    def col_sum(x):
        return jnp.sum(rounded(x), axis=0)

    @jax.jit
    def add_gram(hi, lo, x, mean):
        xc = rounded(rounded(x) - mean[None, :])
        g = jnp.matmul(xc.T, xc, precision=jax.lax.Precision.HIGHEST)
        return _two_sum(hi, lo, g)

    def blocks():
        for c in chunks:
            step = _block_rows(n, c.shape[0])
            for start in range(0, c.shape[0], step):
                yield jax.device_put(c[start:start + step], device)

    total_sum = np.zeros(n, dtype=np.float64)
    for x in blocks():
        total_sum += np.asarray(col_sum(x), dtype=np.float64)
        if round_to is not None:
            total_sum = np.asarray(jnp.asarray(total_sum, dtype=round_to),
                                   dtype=np.float64)
    mean = total_sum / total
    mean_dev = jax.device_put(mean.astype(np.float32), device)
    hi = jnp.zeros((n, n), jnp.float32, device=device)
    lo = jnp.zeros((n, n), jnp.float32, device=device)
    for x in blocks():
        hi, lo = add_gram(hi, lo, x, mean_dev)
    gram = np.asarray(hi, dtype=np.float64) + np.asarray(lo, dtype=np.float64)
    del hi, lo, mean_dev
    return mean, gram / max(total - 1, 1), total


def reference(chunks, device=None) -> dict:
    mean, cov, total = moments(chunks, device)
    cov = (cov + cov.T) / 2
    return {"mean": mean, "cov": cov, "rows": total,
            "trace": float(np.trace(cov)),
            "evals": np.linalg.eigvalsh(cov)[::-1]}


def lower_precision_model(chunks, k: int, device=None,
                          round_to: str = "bfloat16") -> dict:
    """The control: a model from this reference run in ``round_to``."""
    mean, cov, _ = moments(chunks, device, round_to=round_to)
    evals, evecs = np.linalg.eigh((cov + cov.T) / 2)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    return {"pc": evecs[:, :k],
            "explained_variance": np.maximum(evals[:k], 0) / evals.sum(),
            "mean": mean}


def model_of(fitted) -> dict:
    return {"pc": np.asarray(fitted.pc),
            "explained_variance": np.asarray(fitted.explained_variance),
            "mean": np.asarray(fitted.mean)}


def gaps(model: dict, ref: dict) -> dict:
    """The three compared numbers for one fitted model."""
    pc = np.asarray(model["pc"], dtype=np.float64)
    evr = np.asarray(model["explained_variance"], dtype=np.float64)
    mean = np.asarray(model["mean"], dtype=np.float64)
    k = pc.shape[1]
    a = pc.T @ ref["cov"] @ pc
    theta = np.diag(a).copy()
    with np.errstate(divide="ignore", invalid="ignore"):
        ritz = np.abs(evr * ref["trace"] / theta - 1.0)
    return {
        "mean_gap": float(np.max(np.abs(mean - ref["mean"]))
                          / np.max(np.abs(ref["mean"]))),
        # np.max hands a NaN on, which then fails its limit
        "ritz_gap": float(np.max(ritz)),
        "miss_gap": float(1.0 - np.trace(a) / np.sum(ref["evals"][:k])),
    }


def compare(models: list, ref: dict, limits: dict):
    """Worst gap over the window's models beside its limit.

    Returns (correct, {name: {"value": v, "limit": l}}). A NaN, or a name
    without a limit, is not correct."""
    worst: dict = {}
    seen: list = []
    for model in models:
        # fits of one window see the same rows; a model equal to one
        # already compared, bit for bit, reads the same gaps
        if any(all(np.array_equal(model[f], other[f]) for f in
                   ("pc", "explained_variance", "mean")) for other in seen):
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


def _skip_second_call(real):
    calls = {"n": 0}

    def step(acc, *args, **kwargs):
        calls["n"] += 1
        return acc if calls["n"] % 4 == 2 else real(acc, *args, **kwargs)

    return step


def _half_batch(real):
    def step(acc, batch, *args, **kwargs):
        import jax.numpy as jnp

        half = batch[: batch.shape[0] // 2]
        return real(acc, jnp.concatenate([half, half]), *args, **kwargs)

    return step


def break_state_unchanged(monkeypatch):
    """One accumulate step in four hands its state back unchanged."""
    from spark_rapids_ml_tpu.ops import streaming

    for name in ("update_centered_gram_auto", "update_stats_auto"):
        monkeypatch.setattr(streaming, name,
                            _skip_second_call(getattr(streaming, name)))


def break_half_batch(monkeypatch):
    """Every accumulate step (mean pass too) leaves half of its batch out
    and takes the mean and the covariance over the rest. The rest stands
    in twice, so that the row count stays what the program's own guard
    (pass 1 against pass 2) expects."""
    from spark_rapids_ml_tpu.ops import streaming

    for name in ("update_mean_stats", "update_centered_gram_auto",
                 "update_stats_auto"):
        monkeypatch.setattr(streaming, name,
                            _half_batch(getattr(streaming, name)))


def _alter(field):
    def breaker(monkeypatch):
        from spark_rapids_ml_tpu.models import pca

        real = pca.PCAModel.__init__

        def init(self, *args, **kwargs):
            if field == "explained_variance":
                kwargs[field] = np.array(kwargs[field]) * 1.001
            else:
                kwargs[field] = np.array(kwargs[field])
                kwargs[field][..., 0] += 1e-3
            real(self, *args, **kwargs)

        monkeypatch.setattr(pca.PCAModel, "__init__", init)

    breaker.__doc__ = f"The answer's {field} altered where it is produced."
    return breaker


FAULTS = {
    "state_unchanged": break_state_unchanged,
    "half_batch": break_half_batch,
    "pc_altered": _alter("pc"),
    "variance_altered": _alter("explained_variance"),
    "mean_altered": _alter("mean"),
}
