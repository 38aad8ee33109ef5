"""Plain Newton reference and the comparison that decides ``correct`` for a
binary LogisticRegression fitted on the streamed loop
(``configs/logreg-3000-newton.json``).

Imports nothing of the program. The objective is Spark ML's: the 1/n-scaled
log-loss of every row plus λ/2‖w‖² on the coefficients, the intercept
unpenalised. What the model returns (``model_of``) is its coefficients, its
intercept, its steps (``n_iter_``) and the two Params that fix the
objective, ``regParam`` and ``fitIntercept``. The reference runs plain
Newton from w = 0 for the fit's own ``n_iter_`` steps: the rows put on the
chip once, in 65,536-row blocks; per block the scores, the gradient Xᵀr and
the weighted Gram XᵀWX in float32 at ``highest`` matmul precision, fetched
and summed in float64 on the host; the system assembled and solved in
float64 by NumPy. Four numbers:

- ``coef_gap`` = max|w − w_ref| / max|w_ref|;
- ``intercept_gap`` = |b − b_ref| / max(|b_ref|, max|w_ref|);
- ``loss_gap`` = |L(w, b) − L(w_ref, b_ref)| / L(w_ref, b_ref), L the
  regularised mean log-loss, each row's from its float32 score, summed in
  float64;
- ``grad_gap`` = ‖∇L(w, b)‖∞ / ‖∇L(0, 0)‖∞ at the program's own final
  (w, b), by the same float64-summed blocks: the optimality check, which
  does not depend on the trajectory.

``FAULTS`` plant a broken timed path for the tests: the gradient at one
bfloat16 MXU pass (the precision below the configuration's float32: the
control, which moves the fixed point), every step leaving half of its
batch out, the label column shifted by one row within each batch, a kept
batch walked twice in every step after the first, and the intercept's
gradient dropped from the system.
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

REF_BLOCK_ROWS = 1 << 16


def model_of(fitted) -> dict:
    return {"coef": np.asarray(fitted.coefficients),
            "intercept": float(fitted.intercept),
            "n_iter": int(fitted.n_iter_),
            "reg_param": float(fitted.getRegParam()),
            "fit_intercept": bool(fitted.getFitIntercept())}


def reference(chunks, device=None) -> dict:
    """Nothing to compute before a model is there: the rows, put on
    ``device`` on first use, and kept there for every step."""
    return {"chunks": chunks, "device": device, "blocks": None, "runs": {}}


def _blocks(ref: dict) -> list:
    import jax

    if ref["blocks"] is None:
        ref["blocks"] = [
            (jax.device_put(x[start:start + REF_BLOCK_ROWS], ref["device"]),
             jax.device_put(y[start:start + REF_BLOCK_ROWS], ref["device"]))
            for x, y in ref["chunks"]
            for start in range(0, x.shape[0], REF_BLOCK_ROWS)]
    return ref["blocks"]


@lru_cache(maxsize=None)
def _block_fns():
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    @jax.jit
    def scores(x, coef, b):
        return jnp.matmul(x, coef, precision=highest) + b

    @partial(jax.jit, static_argnames=("hessian",))
    def partials(x, y, coef, b, hessian=True):
        p = jax.nn.sigmoid(jnp.matmul(x, coef, precision=highest) + b)
        r, s = p - y, p * (1.0 - p)
        gx = jnp.matmul(x.T, r, precision=highest)
        if not hessian:
            return gx, jnp.sum(r)
        return (gx, jnp.matmul(x.T, x * s[:, None], precision=highest),
                jnp.matmul(x.T, s, precision=highest), jnp.sum(r),
                jnp.sum(s))

    return scores, partials


def _on_chip(w: np.ndarray, ref: dict) -> tuple:
    import jax

    return (jax.device_put(w[:-1].astype(np.float32), ref["device"]),
            jax.device_put(np.float32(w[-1]), ref["device"]))


def _rows(ref: dict) -> int:
    return sum(x.shape[0] for x, _ in ref["chunks"])


def gradient(w: np.ndarray, ref: dict, lam: float, fit_b: bool,
             hessian: bool = False):
    """∇L at w = coefficients ++ intercept (float64), and the Hessian where
    asked; the intercept's entries pinned where there is none."""
    _, partials = _block_fns()
    coef, b = _on_chip(w, ref)
    n = w.shape[0] - 1
    sums = None
    for x, y in _blocks(ref):
        part = [np.asarray(v, dtype=np.float64)
                for v in partials(x, y, coef, b, hessian=hessian)]
        sums = part if sums is None else [a + v for a, v in zip(sums, part)]
    rows = _rows(ref)
    g = np.zeros(n + 1)
    g[:n] = sums[0] / rows + lam * w[:n]
    if fit_b:
        g[n] = sums[3 if hessian else 1] / rows  # Σr
    if not hessian:
        return g
    h = np.zeros((n + 1, n + 1))
    h[:n, :n] = sums[1] / rows + lam * np.eye(n)
    if fit_b:
        h[:n, n] = h[n, :n] = sums[2] / rows
        h[n, n] = sums[4] / rows
    else:
        h[n, n] = 1.0
    return g, h


def loss(w: np.ndarray, ref: dict, lam: float) -> float:
    """The regularised mean log-loss at w: each row's log(1 + e^z) − y z
    from its float32 score z, summed in float64."""
    scores, _ = _block_fns()
    coef, b = _on_chip(w, ref)
    total = 0.0
    for x, y in _blocks(ref):
        z = np.asarray(scores(x, coef, b), dtype=np.float64)
        total += float(np.sum(np.logaddexp(0.0, z)
                              - np.asarray(y, dtype=np.float64) * z))
    return total / _rows(ref) + 0.5 * lam * float(w[:-1] @ w[:-1])


def newton(ref: dict, steps: int, lam: float, fit_b: bool) -> dict:
    """``steps`` plain Newton steps from w = 0, and ‖∇L(0, 0)‖∞; kept in
    ``ref`` for every model of the same steps and objective."""
    key = (steps, lam, fit_b)
    if key not in ref["runs"]:
        n = ref["chunks"][0][0].shape[1]
        w = np.zeros(n + 1)
        g0 = None
        for _ in range(steps):
            g, h = gradient(w, ref, lam, fit_b, hessian=True)
            g0 = np.max(np.abs(g)) if g0 is None else g0
            w = w - np.linalg.solve(h, g)
        if g0 is None:
            g0 = np.max(np.abs(gradient(w, ref, lam, fit_b)))
        ref["runs"][key] = {"w": w, "grad0": g0, "loss": loss(w, ref, lam)}
    return ref["runs"][key]


def gaps(model: dict, ref: dict) -> dict:
    lam, fit_b = model["reg_param"], model["fit_intercept"]
    run = newton(ref, model["n_iter"], lam, fit_b)
    w = np.append(np.asarray(model["coef"], dtype=np.float64),
                  model["intercept"])
    w_ref = run["w"]
    scale = np.max(np.abs(w_ref[:-1]))
    return {
        "coef_gap": float(np.max(np.abs(w[:-1] - w_ref[:-1])) / scale),
        "intercept_gap": abs(w[-1] - w_ref[-1]) / max(abs(w_ref[-1]),
                                                       scale),
        "loss_gap": abs(loss(w, ref, lam) - run["loss"]) / run["loss"],
        "grad_gap": float(np.max(np.abs(gradient(w, ref, lam, fit_b)))
                          / run["grad0"]),
    }


def compare(models: list, ref: dict, limits: dict):
    """Worst gap over the window's distinct models beside its limit:
    (correct, {name: {"value": v, "limit": l}}). A NaN, or a name without
    a limit, is not correct."""
    worst: dict = {}
    seen: list = []
    for model in models:
        # fits of one window see the same rows: bit-equal models once
        if any(np.array_equal(model["coef"], o["coef"])
               and model["intercept"] == o["intercept"] for o in seen):
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


def _wrap_step(monkeypatch, change):
    """Every batch the Newton steps sum handed to ``change(batch, mask)``
    first."""
    from spark_rapids_ml_tpu.ops import logreg_kernel

    real = logreg_kernel.update_logreg_stats

    def step(carry, batch, w, b, mask=None):
        return real(carry, change(batch, mask), w, b, mask)

    monkeypatch.setattr(logreg_kernel, "update_logreg_stats", step)


def break_half_batch(monkeypatch):
    """Every step leaves half of its batch out and counts the rest
    twice."""
    _wrap_step(monkeypatch, _half_of)


def break_label_shift(monkeypatch):
    """The label column shifted by one row within each batch: every row
    summed with its neighbour's label."""
    import jax.numpy as jnp

    _wrap_step(monkeypatch, lambda batch, mask: batch.at[:, -1].set(
        jnp.roll(batch[:, -1], 1)))


def break_kept_twice(monkeypatch):
    """Every step after the first walks the first kept batch twice (the
    keep stood in for where the backend reports no free memory)."""
    from spark_rapids_ml_tpu.ops import streaming

    real = streaming.IngestTrace.replay

    def replay(self, *args, **kwargs):
        first = True
        for item in real(self, *args, **kwargs):
            if first:
                yield item
                first = False
            yield item

    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda *a, **k: 1 << 40)
    monkeypatch.setattr(streaming.IngestTrace, "replay", replay)


def break_intercept_grad_dropped(monkeypatch):
    """The intercept's gradient (Σr) dropped from every step's system."""
    from spark_rapids_ml_tpu.ops import logreg_kernel

    real = logreg_kernel.newton_step

    def step(carry, coef, b, reg_param, fit_intercept=True):
        gx, hxx, hxb, rsum, ssum, cnt = carry
        return real((gx, hxx, hxb, rsum * 0, ssum, cnt), coef, b, reg_param,
                    fit_intercept=fit_intercept)

    monkeypatch.setattr(logreg_kernel, "newton_step", step)


def break_grad_bf16(monkeypatch):
    """The control: the gradient Xᵀr at one bfloat16 MXU pass — both
    operands rounded to bfloat16 (``reduce_precision``, which the compiler
    keeps where it may drop a pair of casts), their exact products summed
    in float32 — the precision below the configuration's float32 at
    ``highest``; the logits and the Hessian as the program has them."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import logreg_kernel

    highest = lax.Precision.HIGHEST

    def bf16(v):
        return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    @partial(jax.jit, donate_argnums=(0,))
    def step(carry, batch, w, b, mask=None):
        x = batch[:, :-1].astype(carry[0].dtype)
        y = batch[:, -1].astype(carry[0].dtype)
        valid = (jnp.ones(x.shape[0], x.dtype) if mask is None
                 else mask.astype(x.dtype))
        p = jax.nn.sigmoid(jnp.matmul(x, w, precision=highest) + b)
        r, s = (p - y) * valid, p * (1.0 - p) * valid
        xs = x * s[:, None]
        return (carry[0] + jnp.matmul(bf16(x).T, bf16(r), precision=highest),
                carry[1] + jnp.matmul(x.T, xs, precision=highest),
                carry[2] + jnp.sum(xs, axis=0), carry[3] + jnp.sum(r),
                carry[4] + jnp.sum(s), carry[5] + jnp.sum(valid))

    monkeypatch.setattr(logreg_kernel, "update_logreg_stats", step)


FAULTS = {
    "grad_bf16": break_grad_bf16,
    "half_batch": break_half_batch,
    "intercept_grad_dropped": break_intercept_grad_dropped,
    "kept_twice": break_kept_twice,
    "label_shift": break_label_shift,
}
