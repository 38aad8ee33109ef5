"""Randomized top-k eigensolver (subspace iteration) for large covariances.

The reference's eigensolve is a dense full-spectrum ``syevd`` on the driver
GPU (``/root/reference/native/src/rapidsml_jni.cu:338-392``), which caps the
feature dimension at whatever one device can factorize. For PCA only the top
k eigenpairs are needed; randomized subspace iteration (Halko-Martinsson-
Tropp) gets them with a handful of tall-skinny matmuls — MXU-friendly,
O(n²·l) instead of O(n³), and the only primitive it needs from the matrix is
``v ↦ Cov·v``. That matvec abstraction is what lets the same solver run on a
replicated covariance (here) or on a feature-sharded covariance where no
device ever holds the full n×n (``parallel/feature_sharded.py``) — the
"feature-dimension scaling" answer sketched in SURVEY.md §5.

All iteration counts are static, so the whole solve jit-compiles into one
XLA program with no host round trips; on the fit path
``ops.eigh.pca_from_covariance_gated`` runs it, the gate's arithmetic
included, as one tracked program. The TPU compiler expands
every ``eigh`` call site on its own, so the iteration is written rolled:
the power iterations are one ``lax.fori_loop`` and the two whitening
passes inside it another, which leaves two ``eigh`` sites (the
whitening's and Rayleigh-Ritz) where the unrolled form had eleven —
compiled for a v5e at n = 4096, k = 256: 23 s and 50 MB of code against
78 s and 267 MB (PERF.md §6, PR 30). Same operations in the same order.

Accuracy caveat (inherent to randomized methods, same as sklearn's
``svd_solver='randomized'``): individual eigenvectors converge at a rate set
by the gaps between consecutive eigenvalues. On decaying spectra — the
regime where PCA is meaningful — a few power iterations reach oracle
accuracy (see tests/test_feature_sharded.py). On near-degenerate spectra
(e.g. isotropic noise) the top-k SUBSPACE is still captured but individual
vectors within a degenerate cluster are arbitrary rotations of each other;
use the dense ``eigh`` solver when exact per-vector parity on gapless
spectra matters.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops.eigh import eigh_descending, sign_flip


def _orthonormalize(y: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal basis of range(Y) via eigh-based whitening.

    ``jnp.linalg.qr`` lowers to a blocked Householder loop that compiles
    pathologically slowly on the TPU backend (minutes-scale at 4096×266,
    measured via a hung finalize); the Gram-eigh route is three MXU matmuls
    plus an l×l eigendecomposition (QDWH — the same primitive the dense
    solver already compiles): B = YᵀY, B = VΛVᵀ, Q = Y·V·Λ^(−1/2).

    Like CholeskyQR this squares the condition number: one pass resolves
    only directions whose squared singular value clears the floor
    ``λmax·eps·n`` — in float32 at n=4096 a singular-value range of 45.
    Y = Cov·Q spans λ₁/λ_l, which is 266 for a 1/j spectrum at l=266, so a
    single pass that DROPPED what it could not resolve zeroed four fifths
    of the basis on the chip (PR 21). Hence two passes: the first clamps —
    directions under the floor are scaled up by it, not dropped — and the
    second, now facing a range of √floor-over-σ at most, resolves them
    (45² ≈ 2000 in all). Only what is still under the floor after both is
    zeroed: a zero column stays zero through later matvecs, Rayleigh-Ritz
    gives it eigenvalue 0 and it sorts last — the honest answer when k
    exceeds rank(Cov), and what ``pca_from_covariance_gated`` looks for
    when it is not.
    """
    eps = jnp.asarray(jnp.finfo(y.dtype).eps, y.dtype)
    tiny = jnp.asarray(jnp.finfo(y.dtype).tiny, y.dtype)

    def whiten(i, y):
        b = y.T @ y
        b = (b + b.T) / 2
        evals, vecs = jnp.linalg.eigh(b)
        # never 0: an all-zero Y (constant data) must come out as zero
        # columns, not 0·inf = NaN
        floor = jnp.maximum(evals[-1] * eps * y.shape[0], tiny)
        inv_sqrt = 1.0 / jnp.sqrt(jnp.maximum(evals, floor))
        # pass 0 clamps, pass 1 drops what is still unresolved
        inv_sqrt = jnp.where((i == 0) | (evals > floor), inv_sqrt, 0.0)
        return y @ (vecs * inv_sqrt[None, :])

    # a loop of two, not two calls: one eigh site to compile
    return lax.fori_loop(0, 2, whiten, y)


def subspace_iteration(
    matvec: Callable[[jnp.ndarray], jnp.ndarray],
    n: int,
    l: int,
    n_iter: int,
    key: jax.Array,
    dtype,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-l eigenpairs of a symmetric PSD operator given only its matvec.

    ``matvec`` maps an (n, l) block to Cov @ block (full rows, whatever the
    caller's covariance layout). Returns (evals[l] descending, evecs[n, l]).
    Re-orthonormalization every step keeps the power iteration stable at
    f32; the Rayleigh-Ritz projection B = QᵀCovQ recovers the eigenvalues.
    """
    # Full f32 matmuls throughout: the iteration's convergence and the
    # Rayleigh-Ritz eigenvalues are sensitive to the single-pass-bf16 TPU
    # default, and these tall-skinny (n×l) products are a rounding error
    # next to the O(n²·rows) Gram that produced the covariance.
    with jax.default_matmul_precision("highest"):
        omega = jax.random.normal(key, (n, l), dtype=dtype)
        y = matvec(omega)

        def step(_, carry):
            q = _orthonormalize(carry[1])
            return q, matvec(q)

        # n_iter power iterations and the closing orthonormalization in
        # one loop; its last Cov·Q is the product Rayleigh-Ritz needs
        q, y = lax.fori_loop(
            0, max(n_iter, 0) + 1, step, (jnp.zeros_like(y), y))
        b = q.T @ y
        b = (b + b.T) / 2  # exact symmetry for eigh
        evals, vecs = eigh_descending(b)
        return evals, q @ vecs


def topk_from_subspace(
    evals: jnp.ndarray,
    evecs: jnp.ndarray,
    k: int,
    total_variance: jnp.ndarray,
    flip_signs: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared postprocessing for randomized solves: sign-flip, top-k
    truncation, λ/Σλ with clamped Rayleigh-Ritz eigenvalues.

    ``total_variance`` (= trace(Cov)) is passed in rather than derived so the
    λ/Σλ denominator stays EXACT while the λᵢ are estimates — sharded
    callers compute the trace with a cheap collective. One implementation so
    the replicated and sharded paths cannot drift.
    """
    if flip_signs:
        evecs = sign_flip(evecs)
    lam = jnp.maximum(evals[:k], 0.0)
    evr = lam / jnp.where(total_variance > 0, total_variance, 1.0)
    return evecs[:, :k], evr


def randomized_pca_from_covariance(
    cov: jnp.ndarray,
    k: int,
    total_variance: jnp.ndarray,
    oversample: int = 10,
    n_iter: int = 4,
    seed: int = 0,
    flip_signs: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(components[n, k], explained_variance_ratio[k]) from a replicated
    covariance, without factorizing the full spectrum."""
    n = cov.shape[0]
    l = min(k + oversample, n)
    evals, evecs = subspace_iteration(
        lambda v: cov @ v, n, l, n_iter, jax.random.PRNGKey(seed), cov.dtype
    )
    return topk_from_subspace(evals, evecs, k, total_variance, flip_signs)
