"""LogisticRegression device kernels: Newton-IRLS in one compiled program.

Third-algorithm coverage beyond the reference (whose roadmap stops at PCA;
KMeans/LinearRegression are BASELINE.md config 5). Binary logistic
regression with L2, in Spark ML's objective convention:

    min_w  (1/n) Σ logloss(yᵢ, σ(xᵢ·w + b)) + (λ/2)·||w||²   (intercept
    unpenalized, like Spark's ``LogisticRegression`` with
    ``elasticNetParam=0``)

solved by Newton-IRLS — each iteration is two MXU matmuls (the logits
``X·w`` and the weighted Hessian ``Xᵀdiag(σ')X``) plus an (n+1)² Cholesky
solve, the same "big matmul + small dense solve" shape as every other
algorithm here. The iteration is a ``lax.while_loop`` compiled into the
program; masked (padding) rows contribute nothing to loss, gradient, or
Hessian. A streamed fit runs the same step as two programs: the partials
folded in a batch at a time (``update_logreg_stats``), then the system
assembled and solved on the chip (``newton_step``) — the assembly and the
Cholesky solve are ``newton_delta`` in both.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.obs.xprof import tracked_jit


class LogRegResult(NamedTuple):
    coefficients: jnp.ndarray   # (n_features,)
    intercept: jnp.ndarray      # scalar
    n_iter: jnp.ndarray         # scalar int
    converged: jnp.ndarray      # scalar bool


def logreg_raw_stats(x, y, coef, b, valid):
    """One batch's Newton partials at (coef, b): (Xᵀr, XᵀWX, Xᵀs, Σr, Σs,
    n) with r = σ(Xw + b) − y and s = σ'(Xw + b) = W's diagonal, masked
    by ``valid``; every product at ``HIGHEST``. Additive across batches
    and shards."""
    dims = (((0,), (0,)), ((), ()))
    p = jax.nn.sigmoid(lax.dot_general(
        x, coef, (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST) + b)
    r = (p - y) * valid                 # residual, masked
    s = p * (1.0 - p) * valid           # IRLS weights, masked
    # Hessian core: Xᵀ diag(s) X — one MXU matmul of the s-scaled rows
    xs = x * s[:, None]
    return (lax.dot_general(x, r, dims, precision=lax.Precision.HIGHEST),
            lax.dot_general(x, xs, dims, precision=lax.Precision.HIGHEST),
            jnp.sum(xs, axis=0), jnp.sum(r), jnp.sum(s), jnp.sum(valid))


def newton_delta(stats, w, reg_param, fit_intercept):
    """The Newton step Δ = H⁻¹g of the Spark-convention objective at ``w``
    from the partials ``stats`` (``logreg_raw_stats``, summed over every
    row), by Cholesky: g and H scaled by 1/n, λ on the coefficients, the
    intercept unpenalised. ``w`` is (n+1,): coefficients ++ intercept slot
    (zero-pinned when ``fit_intercept`` is False: unit diagonal, zero
    gradient). Shared by the in-memory program and the streamed fit's
    finishing program."""
    gx, hxx, hxb, rsum, ssum, cnt = stats
    n_feat = gx.shape[0]
    inv_n = 1.0 / jnp.maximum(cnt, 1.0)
    g = jnp.zeros_like(w)
    g = g.at[:n_feat].set(gx * inv_n + reg_param * w[:n_feat])
    h = jnp.zeros((n_feat + 1, n_feat + 1), dtype=w.dtype)
    h = h.at[:n_feat, :n_feat].set(
        hxx * inv_n + reg_param * jnp.eye(n_feat, dtype=w.dtype)
    )
    if fit_intercept:
        g = g.at[n_feat].set(rsum * inv_n)
        h = h.at[:n_feat, n_feat].set(hxb * inv_n)
        h = h.at[n_feat, :n_feat].set(hxb * inv_n)
        h = h.at[n_feat, n_feat].set(ssum * inv_n)
    else:
        h = h.at[n_feat, n_feat].set(1.0)
    # Damped-free Newton; the ridge term (or the pinned intercept slot)
    # keeps H positive definite.
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(h), g)


def newton_iterations(
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    reg_param: float,
    fit_intercept: bool,
    max_iter: int,
    tol: float,
    reduce_fn=lambda t: t,
) -> LogRegResult:
    dtype = x.dtype
    valid = (
        jnp.ones(x.shape[0], dtype=dtype) if mask is None
        else mask.astype(dtype)
    )
    n_feat = x.shape[1]
    w0 = jnp.zeros((n_feat + 1,), dtype=dtype)

    def step(state):
        w, _, it, _ = state
        # ``reduce_fn`` combines the per-shard partials: identity on one
        # device, ``psum`` over the mesh in the distributed form
        stats = reduce_fn(logreg_raw_stats(x, y, w[:n_feat], w[n_feat],
                                           valid))
        delta = newton_delta(stats, w, reg_param, fit_intercept)
        w_new = w - delta
        moved = jnp.max(jnp.abs(delta))
        return w_new, moved, it + 1, moved <= tol

    def cond(state):
        _, _, it, done = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(done))

    init = (w0, jnp.asarray(jnp.inf, dtype=dtype),
            jnp.asarray(0, dtype=jnp.int32), jnp.asarray(False))
    w, _, n_iter, converged = lax.while_loop(cond, step, init)
    return LogRegResult(w[:n_feat], w[n_feat], n_iter, converged)


@partial(jax.jit, static_argnames=("fit_intercept", "max_iter"))
def logreg_fit_kernel(
    x: jnp.ndarray,
    y: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 100,
    tol: float = 1e-8,
) -> LogRegResult:
    return newton_iterations(
        x, y, mask, reg_param, fit_intercept, max_iter, tol
    )


def init_logreg_carry(n: int, dtype, device=None):
    """The (gx, hxx, hxb, rsum, ssum, cnt) accumulator of
    ``update_logreg_stats``, zero, on ``device`` — ONE site for the carry
    contract."""
    return tuple(jnp.zeros(shape, dtype=dtype, device=device)
                 for shape in ((n,), (n, n), (n,), (), (), ()))


@partial(jax.jit, donate_argnums=(0,))
def update_logreg_stats(carry, batch_z, w, b, mask=None):
    """Out-of-core Newton building block: fold one ``[X | y]`` batch's
    (Xᵀr, XᵀWX, Xᵀs, Σr, Σs, n) partials at the current (w, b) into a
    donated accumulator. One streamed pass with this per batch = one
    Newton gradient/Hessian evaluation over the full dataset."""
    x = batch_z[:, :-1].astype(carry[0].dtype)
    y = batch_z[:, -1].astype(carry[0].dtype)
    valid = (
        jnp.ones(x.shape[0], dtype=x.dtype) if mask is None
        else mask.astype(x.dtype)
    )
    return tuple(a + s for a, s in
                 zip(carry, logreg_raw_stats(x, y, w, b, valid)))


@partial(tracked_jit, static_argnames=("fit_intercept",))
def newton_step(carry, coef, b, reg_param, fit_intercept: bool = True):
    """The end of one streamed Newton step: the system assembled from the
    summed partials ``carry`` and solved on the chip (``newton_delta``),
    then (coefficients − Δ, intercept − Δ's last, max|Δ|). One program,
    whose scalar the fit reads."""
    w = jnp.concatenate([coef, b[None]])
    delta = newton_delta(carry, w, reg_param, fit_intercept)
    w = w - delta
    return w[:-1], w[-1], jnp.max(jnp.abs(delta))


def step_reserve_bytes(batch_rows: int, width: int, itemsize: int) -> int:
    """Device bytes a streamed Newton step needs beside the batches a fit
    keeps (``ops.streaming.keep_budget_bytes``' reserve), for ``[X | y]``
    batches ``width`` = features + 1 wide: one batch in flight beyond those
    kept, the step's two batch-sized temporaries should the compiler hold
    them (X cut from the batch and its s-scaled copy; the v5e's fuses both
    away), and two width² accumulators (the carry and its donated
    successor)."""
    return itemsize * (3 * batch_rows * width + 2 * width * width)


@tracked_jit
def logreg_predict_kernel(x, coefficients, intercept):
    """Class probabilities σ(X·w + b) — one batched MXU matmul (the
    enabled-batch-transform posture shared with PCAModel.transform).
    Tracked so serving calls carry compile/recompile attribution like the
    PCA/KMeans transform kernels."""
    return jax.nn.sigmoid(x @ coefficients + intercept)


# Pipelined-serving variants (LogisticRegressionModel
# .serving_transform_program): donated staged input for the *_serve form
# (the pipeline never re-reads a staged buffer; retries re-stage from host
# rows), plus env-gated reduced-precision logit GEMMs — the sigmoid always
# runs in f32, only the X·w contraction drops precision.


def _predict_sigmoid(x, coefficients, intercept):
    return jax.nn.sigmoid(x @ coefficients + intercept)


logreg_predict_serve = tracked_jit(
    _predict_sigmoid, label="logreg_predict_serve", donate_argnums=(0,)
)


def _predict_bf16(x, coefficients_bf16, intercept):
    """Coefficients arrive PRE-CAST (staged once at program build)."""
    z = lax.dot_general(
        x.astype(jnp.bfloat16),
        coefficients_bf16[:, None],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]
    return jax.nn.sigmoid(z + intercept.astype(jnp.float32))


logreg_predict_bf16 = tracked_jit(_predict_bf16, label="logreg_predict_bf16")


def _predict_int8(x, coefficients_q, coefficients_scale, intercept):
    """Coefficients arrive PRE-QUANTIZED (``quantize_symmetric_host``);
    only the batch pays the quantization reduction per call."""
    from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric

    xq, sx = quantize_symmetric(x)
    z = lax.dot_general(
        xq, coefficients_q[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )[:, 0].astype(jnp.float32) * (sx * coefficients_scale)
    return jax.nn.sigmoid(z + intercept.astype(jnp.float32))


logreg_predict_int8 = tracked_jit(_predict_int8, label="logreg_predict_int8")

# Un-jitted stage bodies for the fused whole-pipeline serving programs
# (models._serving.build_fused_pipeline_program). σ(X·w+b) is
# output-typed (probabilities), so logreg composes only as the TERMINAL
# stage of a fused chain.
SERVING_STAGE_BODIES = {
    "native": _predict_sigmoid,
    "bf16": _predict_bf16,
    "int8": _predict_int8,
}


# -- multinomial (softmax) family ------------------------------------------
# Spark's LogisticRegression auto-selects multinomial when the label has
# more than two classes. Parameterization matches Spark/sklearn: one
# coefficient row per class (over-parameterized "symmetric" softmax, made
# identifiable by the L2 term), objective
#   (1/Σw)·Σᵢ wᵢ·CE(softmax(Wxᵢ+b), yᵢ) + (λ/2)·‖W‖²  (intercepts free).
# Full Newton on the (K·(d+1)) system: the Hessian's (k,l) feature block
# is Xᵀ diag(wᵢ·(pₖδ(k=l) − pₖpₗ)) X — K² small MXU Grams per iteration,
# fine for the K ≲ tens regime this targets.


class MultinomialResult(NamedTuple):
    coefficients: jnp.ndarray  # (K, n_features)
    intercepts: jnp.ndarray    # (K,)
    n_iter: jnp.ndarray
    converged: jnp.ndarray


def multinomial_raw_stats(wb, x, y_oh, valid):
    """Per-batch RAW softmax-Newton partials at the current (K, d+1)
    parameters: (gxa = rᵀ[x,1] (K, d+1), h_raw = the K²·(d+1)² block
    Hessian numerator, cnt = Σvalid). Additive across batches/shards —
    the accumulation unit for the streamed multinomial fit."""
    n_feat = x.shape[1]
    k = y_oh.shape[1]
    w = wb[:, :n_feat]
    b = wb[:, n_feat]
    z = x @ w.T + b[None, :]
    p = jax.nn.softmax(z, axis=1)
    r = (p - y_oh) * valid[:, None]          # (n, K)
    ones = jnp.ones((x.shape[0], 1), dtype=x.dtype)
    xa = jnp.concatenate([x, ones], axis=1)   # (n, d+1)
    gxa = lax.dot_general(
        r, xa, (((0,), (0,)), ((), ())), precision=lax.Precision.HIGHEST
    )

    def block(kl):
        kk, ll = kl // k, kl % k
        sblk = p[:, kk] * ((kk == ll) * 1.0 - p[:, ll]) * valid
        return lax.dot_general(
            xa * sblk[:, None], xa, (((0,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
        )

    blocks = jax.vmap(block)(jnp.arange(k * k))  # (K², d+1, d+1)
    h_raw = jnp.transpose(
        blocks.reshape(k, k, n_feat + 1, n_feat + 1), (0, 2, 1, 3)
    ).reshape(k * (n_feat + 1), k * (n_feat + 1))
    return gxa, h_raw, jnp.sum(valid)


def assemble_multinomial_system(gxa, h_raw, cnt, wb, reg_param,
                                fit_intercept):
    """(g, h) of the softmax Newton system from accumulated raw partials
    — regularization, intercept pinning, and the gauge ridge live HERE,
    once, shared by the in-memory kernel and the streamed assembler
    (jnp ops: traced inside jit, eager on host arrays)."""
    k, dim = wb.shape
    n_feat = dim - 1
    dtype = h_raw.dtype
    cnt = jnp.maximum(cnt, 1.0)
    w = wb[:, :n_feat]
    g = gxa / cnt
    g = g.at[:, :n_feat].add(reg_param * w)
    if not fit_intercept:
        g = g.at[:, n_feat].set(0.0)
    h = h_raw / cnt
    if not fit_intercept:
        # Pin the intercept slots COMPLETELY: zero their rows and columns,
        # identity diagonal. Zeroing only the gradient would still let
        # Newton steps couple features to implicit intercepts through the
        # off-diagonal Hessian blocks and silently train the wrong model.
        keep = jnp.tile(
            jnp.concatenate([
                jnp.ones((n_feat,), dtype=dtype),
                jnp.zeros((1,), dtype=dtype),
            ]),
            k,
        )
        h = h * keep[:, None] * keep[None, :]

    # L2 on coefficients. The softmax parameterization is invariant under
    # a uniform shift of all K (unpenalized) intercepts — an EXACT null
    # direction for any reg_param — and at reg_param=0 the class-shifted
    # coefficient direction joins it. Pin the gauge with a dtype-scaled
    # ridge (sqrt(eps) × the Hessian's diagonal scale): predictions are
    # invariant to the gauge, and the ridge is far above float32 rounding
    # (a fixed 1e-8 underflows into H in f32 and leaves the system
    # exactly singular).
    eps_ridge = jnp.sqrt(jnp.finfo(dtype).eps).astype(dtype) * (
        jnp.maximum(jnp.mean(jnp.diagonal(h)), 1.0)
    )
    reg_diag = jnp.tile(
        jnp.concatenate([
            jnp.full((n_feat,), reg_param, dtype=dtype),
            jnp.asarray([0.0 if fit_intercept else 1.0], dtype=dtype),
        ]),
        k,
    )
    h = h + jnp.diag(reg_diag) + eps_ridge * jnp.eye(k * dim, dtype=dtype)
    return g, h


def _softmax_grad_hess(wb, x, y_oh, valid, reg_param, fit_intercept):
    gxa, h_raw, cnt = multinomial_raw_stats(wb, x, y_oh, valid)
    return assemble_multinomial_system(
        gxa, h_raw, cnt, wb, reg_param, fit_intercept
    )


@partial(jax.jit, donate_argnums=(0,))
def update_multinomial_stats(carry, x, y_oh, wb, mask=None):
    """Out-of-core softmax-Newton building block: fold one batch's raw
    partials at the current parameters into a donated accumulator. One
    streamed pass = one Newton gradient/Hessian evaluation."""
    gxa, h_raw, cnt = carry
    valid = (
        jnp.ones(x.shape[0], dtype=x.dtype) if mask is None
        else mask.astype(x.dtype)
    )
    g, h, c = multinomial_raw_stats(wb, x.astype(gxa.dtype),
                                    y_oh.astype(gxa.dtype), valid)
    return gxa + g, h_raw + h, cnt + c


@partial(
    jax.jit,
    static_argnames=("fit_intercept", "max_iter", "n_classes"),
)
def multinomial_fit_kernel(
    x: jnp.ndarray,
    y_onehot: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    reg_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 25,
    tol: float = 1e-6,
    n_classes: int = 2,
) -> MultinomialResult:
    dtype = x.dtype
    n_feat = x.shape[1]
    valid = (
        jnp.ones(x.shape[0], dtype=dtype) if mask is None
        else mask.astype(dtype)
    )
    wb0 = jnp.zeros((n_classes, n_feat + 1), dtype=dtype)

    def cond(state):
        wb, i, delta = state
        return jnp.logical_and(i < max_iter, delta > tol)

    def body(state):
        wb, i, _ = state
        g, h = _softmax_grad_hess(
            wb, x, y_onehot, valid, reg_param, fit_intercept
        )
        step = jax.scipy.linalg.cho_solve(
            jax.scipy.linalg.cho_factor(h), g.reshape(-1)
        ).reshape(n_classes, n_feat + 1)
        wb = wb - step
        return wb, i + 1, jnp.max(jnp.abs(step))

    wb, n_iter, delta = lax.while_loop(
        cond, body, (wb0, jnp.asarray(0), jnp.asarray(jnp.inf, dtype))
    )
    return MultinomialResult(
        coefficients=wb[:, :n_feat],
        intercepts=wb[:, n_feat] * (1.0 if fit_intercept else 0.0),
        n_iter=n_iter,
        converged=delta <= tol,
    )
