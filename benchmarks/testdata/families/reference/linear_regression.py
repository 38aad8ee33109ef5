"""Plain least-squares reference (a test fixture) and the comparison that
decides ``correct`` for a LinearRegression fitted from ``(X, y)`` chunks.

Imports nothing of the program. The reference forms the Gram of
``[X | 1 | y]`` over the same host chunks in plain ``jax.numpy`` float32 at
``highest`` matmul precision, in blocks of rows summed by an error-free
two-sum, then centres it and solves the normal equations in float64 on the
host (no regularisation, an intercept): exactly what the configuration's
guarantee states.

What is compared is the model the timed fits returned (``model_of``:
``coefficients`` and ``intercept``) by two numbers:

- ``coef_gap``: max |w - w_ref| over max |w_ref|;
- ``intercept_gap``: |b - b_ref| over the labels' standard deviation.

``FAULTS`` plant a broken timed path for the tests: the answer altered
where the model is made, and every accumulate step leaving half of its
batch out.
"""

from __future__ import annotations

import numpy as np

REF_BLOCK_ROWS = 1 << 16


def _two_sum(hi, lo, v):
    t = hi + v
    bb = t - hi
    return t, lo + ((hi - (t - bb)) + (v - bb))


def model_of(fitted) -> dict:
    return {"coefficients": np.asarray(fitted.coefficients),
            "intercept": np.asarray(fitted.intercept)}


def reference(chunks, device=None) -> dict:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def add_gram(hi, lo, x, y):
        z = jnp.concatenate([x, jnp.ones_like(y)[:, None], y[:, None]], 1)
        return _two_sum(hi, lo, jnp.matmul(
            z.T, z, precision=jax.lax.Precision.HIGHEST))

    n = chunks[0][0].shape[1]
    hi = jnp.zeros((n + 2, n + 2), jnp.float32, device=device)
    lo = jnp.zeros((n + 2, n + 2), jnp.float32, device=device)
    for x, y in chunks:
        for start in range(0, x.shape[0], REF_BLOCK_ROWS):
            part = slice(start, start + REF_BLOCK_ROWS)
            hi, lo = add_gram(hi, lo, jax.device_put(x[part], device),
                              jax.device_put(y[part], device))
    g = np.asarray(hi, dtype=np.float64) + np.asarray(lo, dtype=np.float64)
    rows = g[n, n]
    mean = g[n, :] / rows                    # [mean x, 1, mean y]
    cov = g / rows - np.outer(mean, mean)
    coef = np.linalg.solve(cov[:n, :n], cov[:n, n + 1])
    return {"coefficients": coef,
            "intercept": mean[n + 1] - mean[:n] @ coef,
            "y_std": float(np.sqrt(cov[n + 1, n + 1]))}


def gaps(model: dict, ref: dict) -> dict:
    coef = np.asarray(model["coefficients"], dtype=np.float64)
    intercept = float(np.asarray(model["intercept"], dtype=np.float64))
    return {
        "coef_gap": float(np.max(np.abs(coef - ref["coefficients"]))
                          / np.max(np.abs(ref["coefficients"]))),
        "intercept_gap": abs(intercept - ref["intercept"]) / ref["y_std"],
    }


def compare(models: list, ref: dict, limits: dict):
    """Worst gap over the window's models beside its limit: (correct,
    {name: {"value": v, "limit": l}}). A NaN, or a name without a limit,
    is not correct."""
    worst: dict = {}
    for model in models:
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


def break_coefficients_altered(monkeypatch):
    """The answer's coefficients altered where the model is made."""
    from spark_rapids_ml_tpu.models import linear_regression as lr

    real = lr.LinearRegressionModel.__init__

    def init(self, *args, **kwargs):
        kwargs["coefficients"] = np.array(kwargs["coefficients"]) * 1.001
        real(self, *args, **kwargs)

    monkeypatch.setattr(lr.LinearRegressionModel, "__init__", init)


def break_half_batch(monkeypatch):
    """Every accumulate step leaves half of its batch out and counts the
    rest twice."""
    from spark_rapids_ml_tpu.ops import streaming

    real = streaming.update_stats

    def step(stats, batch, mask=None, *args, **kwargs):
        return real(stats, _half_of(batch, mask), mask, *args, **kwargs)

    monkeypatch.setattr(streaming, "update_stats", step)


FAULTS = {
    "coefficients_altered": break_coefficients_altered,
    "half_batch": break_half_batch,
}
