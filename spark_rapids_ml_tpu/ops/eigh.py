"""Eigendecomposition + PCA postprocessing, fused into one XLA program.

Replaces the reference's driver-GPU ``calSVD`` native kernel
(``/root/reference/native/src/rapidsml_jni.cu:338-392``): RAFT ``eigDC``
(cuSolver syevd) → colReverse/rowReverse → S←√S → Thrust signFlip. Here the
whole chain — ``eigh``, descending reorder, sign-flip, explained-variance —
is one jitted program; XLA fuses the postprocessing into a few vector ops.

Two ways in. ``pca_from_covariance`` is the plain traceable function that
kernels inline under their own ``jit``. ``pca_from_covariance_gated`` is
what the fit paths call with a concrete covariance: whichever solver it
settles on runs as ONE tracked program (``_randomized_solve_program`` with
the residual gate's arithmetic inside, or ``_dense_solve_program``); the
host reads the gate's two scalars once and only then, on failure, calls
the dense program — so a fit whose gate passes never compiles the dense
``eigh`` (≈4.5 min at n = 4096 on the v5e).

Semantic corrections vs the reference (SURVEY.md §3.6):
* explained variance is λ/Σλ (Spark CPU semantics), not √λ/Σ√λ
  (the reference GPU path's known inconsistency,
  ``RapidsRowMatrix.scala:101-102`` + ``rapidsml_jni.cu:377``);
* the sign-flip convention (each component's max-|·| coordinate positive,
  ``rapidsml_jni.cu:37-64``) is kept — it makes results deterministic and
  matches sklearn.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.obs.report import current_fit
from spark_rapids_ml_tpu.obs.xprof import tracked_jit


def eigh_descending(cov: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric eigendecomposition with eigenvalues in descending order.

    ``jnp.linalg.eigh`` returns ascending order; the reference reverses with
    ``colReverse``/``rowReverse`` (``rapidsml_jni.cu:374-375``) — here it is a
    negative-stride gather XLA folds away.
    """
    evals, evecs = jnp.linalg.eigh(cov)
    return evals[::-1], evecs[:, ::-1]


def sign_flip(evecs: jnp.ndarray) -> jnp.ndarray:
    """Flip each column's sign so its max-|·| entry is positive.

    Vectorized equivalent of the reference's Thrust ``signFlip`` kernel
    (``rapidsml_jni.cu:37-64``): one argmax + gather + broadcast multiply,
    no per-column loop.
    """
    idx = jnp.argmax(jnp.abs(evecs), axis=0)
    picked = evecs[idx, jnp.arange(evecs.shape[1])]
    signs = jnp.where(picked < 0, -1.0, 1.0).astype(evecs.dtype)
    return evecs * signs[None, :]


def explained_variance_ratio(evals: jnp.ndarray) -> jnp.ndarray:
    """λᵢ/Σλ over all eigenvalues (clamped at 0 for tiny negatives).

    Denominator is the sum over ALL eigenvalues; truncation to k happens
    after, as in ``RapidsRowMatrix.scala:101-109``.
    """
    lam = jnp.maximum(evals, 0.0)
    total = jnp.sum(lam)
    return lam / jnp.where(total > 0, total, 1.0)


def eigh_postprocess_host(evals, evecs):
    """NumPy version of the descending-reorder + sign-flip chain — same
    semantics as the XLA chain above, shared by every host fallback (PCA,
    TruncatedSVD) so the conventions can't drift. Takes LAPACK
    ascending-order output; returns (evals_descending, evecs_flipped)."""
    import numpy as np

    evals = np.asarray(evals)[::-1]
    evecs = np.asarray(evecs)[:, ::-1]
    idx = np.argmax(np.abs(evecs), axis=0)
    signs = np.where(evecs[idx, np.arange(evecs.shape[1])] < 0, -1.0, 1.0)
    return evals, evecs * signs[None, :]


def pca_postprocess_host(evals, evecs, k: int):
    """Host postprocessing for PCA: reorder/flip + λ/Σλ + top-k."""
    import numpy as np

    evals, evecs = eigh_postprocess_host(evals, evecs)
    lam = np.maximum(evals, 0.0)
    total = lam.sum()
    evr = lam / (total if total > 0 else 1.0)
    return evecs[:, :k], evr[:k]


def resolve_auto_solver(n: int, k: int) -> str:
    """Static solver choice for ``solver='auto'``: randomized top-k when
    k ≪ n on a covariance big enough for the O(n³) eigh to matter
    (measured ~1.4s at n=4096 on a v5e vs 0.37s randomized), dense eigh
    otherwise. Shape-only, so it is jit-safe (resolves at trace time)."""
    return "randomized" if (n >= 1024 and k * 8 <= n) else "eigh"


def pca_from_covariance(
    cov: jnp.ndarray, k: int, flip_signs: bool = True, solver: str = "eigh"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(components[n,k], explained_variance_ratio[k]) from covariance.

    ``k`` is static (compile-time), matching the top-k truncation
    ``Arrays.copyOfRange(u.data, 0, n*k)`` (``RapidsRowMatrix.scala:104-109``).

    ``solver``:
    * ``"eigh"`` (default) — dense full-spectrum factorization, exact
      per-vector parity with the LAPACK/Spark oracle. O(n³), and the fixed
      cost that dominates small-row fits (measured 0.9s at n=4096 on a
      v5e).
    * ``"randomized"`` — Halko-Martinsson-Tropp subspace iteration for the
      top k only (``ops.randomized``): a chain of tall-skinny MXU matmuls,
      O(n²·k), ~100× faster at n=4096 k=256. The λ/Σλ denominator stays
      EXACT via trace(cov). Per-vector accuracy depends on spectral gaps —
      see the accuracy caveat in ``ops/randomized.py``; use on decaying
      spectra (the regime where PCA is meaningful).
    * ``"auto"`` — ``resolve_auto_solver`` picks between them by shape.
      Under jit the choice is static and unverified; eager callers should
      prefer ``pca_from_covariance_gated``, which adds the residual check.
    """
    if solver == "auto":
        solver = resolve_auto_solver(cov.shape[0], k)
    if solver == "randomized":
        from spark_rapids_ml_tpu.ops.randomized import (
            randomized_pca_from_covariance,
        )

        return randomized_pca_from_covariance(
            cov, k, jnp.trace(cov), flip_signs=flip_signs
        )
    if solver != "eigh":
        raise ValueError(
            f"solver={solver!r}: expected 'eigh', 'randomized', or 'auto'"
        )
    evals, evecs = eigh_descending(cov)
    if flip_signs:
        evecs = sign_flip(evecs)
    evr = explained_variance_ratio(evals)
    return evecs[:, :k], evr[:k]


@partial(tracked_jit, static_argnames=("k", "flip_signs"))
def _dense_solve_program(cov, k, flip_signs):
    """The dense branch as one program: ``eigh``, reorder, sign-flip,
    λ/Σλ and the top-k slice."""
    return pca_from_covariance(cov, k, flip_signs, "eigh")


@partial(tracked_jit, static_argnames=("k", "flip_signs", "solve"))
def _randomized_solve_program(cov, k, flip_signs, solve: Callable):
    """The randomized branch and the gate's arithmetic as one program:
    ``(pc, evr, residual_ratio, min_column_norm²)``. ``solve`` is
    ``ops.randomized.randomized_pca_from_covariance`` as the caller found
    it in the module; being static it keys the program, so a replaced
    solver (``benchmarks/sweep.py`` plants one) is traced anew instead of
    answered from the cache under the replacement's name."""
    trace = jnp.trace(cov)
    pc, evr = solve(cov, k, trace, flip_signs=flip_signs)
    lam = evr * trace
    resid = jnp.linalg.norm(cov @ pc - pc * lam[None, :])
    scale = jnp.sqrt(jnp.asarray(k, cov.dtype)) * jnp.maximum(
        jnp.mean(lam), jnp.finfo(cov.dtype).tiny
    )
    return pc, evr, resid / scale, jnp.min(jnp.sum(pc * pc, axis=0))


def pca_from_covariance_gated(
    cov: jnp.ndarray,
    k: int,
    flip_signs: bool = True,
    solver: str = "auto",
    residual_rtol: float = 0.05,
) -> Tuple[jnp.ndarray, jnp.ndarray, str]:
    """``pca_from_covariance`` with the eigh-vs-randomized residual gate.

    For call sites that hold a concrete covariance — the model fit paths,
    not jitted kernels: the verdict is read on the host (one read of two
    scalars). When the shape heuristic picks randomized, the eigenpair
    residual ``‖Cov·V − V·Λ‖_F / (√k · mean(λ))`` is computed in the
    solve's own program; if it exceeds ``residual_rtol`` (catastrophic
    non-convergence — a slow-decay tail the subspace iteration didn't
    capture), the dense eigh program is run and its result returned
    instead. Sub-threshold wobble on near-degenerate spectra is rotation
    within an eigenvalue cluster — a legitimate PCA basis capturing the
    same variance — and intentionally passes.

    The residual alone is blind to a DROPPED direction: a zero column has
    zero residual. The orthonormalization zeroes what float32 cannot
    resolve (``ops.randomized._orthonormalize``), so the gate also requires
    every returned component to have unit norm; a basis with a dropped
    column — a spectrum steeper than the whitening's range, or k beyond
    rank(Cov) — takes the dense fallback too.

    Returns ``(components, evr, solver_used)`` and notes
    ``solve={solver, gate, residual_ratio, programs}`` on the fit's report
    (``programs``: tracked programs called, 2 after a fallback).
    """
    if solver == "auto":
        solver = resolve_auto_solver(cov.shape[0], k)
    if isinstance(cov, jax.core.Tracer):
        # under jit the gate's D2H read is impossible; take the static
        # choice ungated (same behavior as pca_from_covariance('auto'))
        pc, evr = pca_from_covariance(cov, k, flip_signs, solver)
        return pc, evr, solver
    if solver not in ("eigh", "randomized"):
        # the plain function's to refuse, before any program
        return (*pca_from_covariance(cov, k, flip_signs, solver), solver)
    ratio, programs = None, 1
    if solver == "eigh":
        pc, evr = _dense_solve_program(cov, k, flip_signs)
        used, gate = "eigh", "ungated"
    else:
        from spark_rapids_ml_tpu.ops import randomized

        pc, evr, ratio, min_norm2 = _randomized_solve_program(
            cov, k, flip_signs, randomized.randomized_pca_from_covariance
        )
        ratio, min_norm2 = (
            float(v) for v in jax.device_get((ratio, min_norm2)))
        used, gate = "randomized", "passed"
        # the comparison is inverted so NaN/inf residuals (overflowed
        # solve) FAIL the gate rather than slipping through a
        # `NaN > rtol` == False
        if not (ratio <= residual_rtol and min_norm2 > 0.5):
            pc, evr = _dense_solve_program(cov, k, flip_signs)
            used, gate, programs = "eigh(gated)", "fallback", 2
    current_fit().note(solve={"solver": used, "gate": gate,
                              "residual_ratio": ratio, "programs": programs})
    return pc, evr, used
