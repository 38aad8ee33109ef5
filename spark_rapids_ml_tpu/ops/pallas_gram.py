"""Pallas TPU kernel: fused center+scale+mask+Gram.

The covariance pipeline's HBM-bandwidth hazard is materializing the centered
matrix ``(X−μ)·s`` before the Gram matmul — an extra full read+write of X.
XLA usually fuses the subtraction into the matmul's operand load; this
kernel makes that guarantee explicit and adds the row-mask multiply in the
same pass: X is read from HBM exactly once per (i,j) output tile pair, the
center/scale/mask arithmetic happens in VMEM, and the MXU accumulates
``Gᵢⱼ += x̃ᵢᵀ x̃ⱼ`` tile by tile.

Grid: (row_tiles as the MINOR axis for revisiting-accumulation, col_tile_i,
col_tile_j). Output tile (i,j) is initialized on the first row tile and
accumulated across the rest — the standard Pallas reduction pattern.

Used on TPU when shapes are tile-aligned; everywhere else the XLA
``covariance`` path is identical semantics (tests assert equality in
interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_ml_tpu.obs.xprof import tracked_jit


# f32 min tile is (8,128). Block sizes were swept on a live TPU v5e
# (bn×br ∈ {256,512,1024,2048}×{512,1024,2048,4096}, 65536×4096 batches):
# 512×1024 wins (2.29M rows/s in the donated-accumulator bench; 256×512
# manages only ~0.4M — small output tiles starve the MXU between grid
# steps) and 2048-wide blocks fail to compile. Scoped-VMEM cost at
# 512×1024: double-buffered f32 inputs 2×2×(1024×512×4B) = 8 MB, bf16
# hi/lo split temps 4×(1024×512×2B) = 4 MB, f32 acc + output staging
# ≈ 2 MB, mean/rowmul slivers — ≈ 17 MB total, past the 16 MB default
# scoped limit, hence the vmem_limit_bytes override on the pallas_call.
_BLOCK_N = 512
_BLOCK_R = 1024


def _make_gram_kernel(precision, symmetric):
    # Precision follows the SAME policy as the XLA gram()
    # (TPUML_GRAM_PRECISION, default bfloat16_3x) so the bench A/B against
    # lax.dot_general compares kernels doing identical MXU work. Mosaic's
    # dot lowering accepts only DEFAULT/HIGHEST, so the 3-pass bf16 split
    # (== lax.Precision.HIGH) is spelled out by hand: x = hi + lo in bf16,
    # accumulate hiᵀhi + hiᵀlo + loᵀhi in f32 and drop the O(ε²) loᵀlo term.
    split_bf16 = precision in ("bfloat16_3x", "high", jax.lax.Precision.HIGH)
    hw_precision = (
        jax.lax.Precision.DEFAULT if split_bf16 else precision
    )

    def _dot_t(a, b, acc_dtype):
        return jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())),
            precision=hw_precision,
            preferred_element_type=acc_dtype,
        )

    del symmetric  # tile selection lives in the grid/index maps, not here

    def _gram_kernel(x_i_ref, x_j_ref, mean_i_ref, mean_j_ref, rowmul_ref,
                     o_ref):
        r = pl.program_id(2)

        @pl.when(r == 0)
        def _init():
            o_ref[:] = jnp.zeros_like(o_ref)

        m = rowmul_ref[:]  # (BLOCK_R, 1): mask × 1/√(n−1), 0 on padding
        xi = (x_i_ref[:] - mean_i_ref[:]) * m
        xj = (x_j_ref[:] - mean_j_ref[:]) * m
        if split_bf16:
            xi_hi = xi.astype(jnp.bfloat16)
            xj_hi = xj.astype(jnp.bfloat16)
            xi_lo = (xi - xi_hi.astype(xi.dtype)).astype(jnp.bfloat16)
            xj_lo = (xj - xj_hi.astype(xj.dtype)).astype(jnp.bfloat16)
            acc = _dot_t(xi_hi, xj_hi, o_ref.dtype)
            acc += _dot_t(xi_hi, xj_lo, o_ref.dtype)
            acc += _dot_t(xi_lo, xj_hi, o_ref.dtype)
            o_ref[:] += acc
        else:
            o_ref[:] += _dot_t(xi, xj, o_ref.dtype)

    return _gram_kernel


def _folded_triangle_maps(n_tiles):
    """Index maps for a folded triangular grid over a T×T symmetric output.

    The upper triangle (j ≥ i) has T(T+1)/2 tiles. Pairing row p with row
    T−1−p gives every pair exactly T+1 tiles — row p contributes its T−p
    upper tiles, row T−1−p its p+1 — so a rectangular grid of
    ceil(T/2) × (T+1) covers the triangle with no dead cells: half the MXU
    work AND half the block fetches of the full grid (a skip-with-pl.when
    variant still streams the dead tiles' operands; measured memory-bound
    on a v5e at exactly the full grid's HBM time).

    For odd T the fold pairs the middle row with itself; the q ≥ T−p branch
    then revisits tiles of row p = T−1−p that the first branch already
    covers. Those duplicates would double-accumulate, so the caller must
    keep T even (pad features by one extra block if needed).
    """
    t = n_tiles

    def _ij(p, q):
        in_first = q < t - p
        i = jnp.where(in_first, p, t - 1 - p)
        j = jnp.where(in_first, p + q, q - (t - p) + t - 1 - p)
        return i, j

    return _ij


@functools.partial(
    tracked_jit,
    static_argnames=(
        "interpret", "precision", "symmetric", "block_n", "block_r"
    ),
)
def _fused_centered_gram(
    x: jnp.ndarray,
    mean: jnp.ndarray,
    rowmul: jnp.ndarray,
    interpret: bool = False,
    precision=None,
    symmetric: bool = True,
    block_n: int = _BLOCK_N,
    block_r: int = _BLOCK_R,
) -> jnp.ndarray:
    """``(diag(rowmul)·(X − mean))ᵀ (diag(rowmul)·(X − mean))`` in one pass.

    ``rowmul`` is the per-row multiplier (mask × global 1/√(n−1) scaling —
    the reference folded the same normalizer into rows before its GEMM,
    ``RapidsRowMatrix.scala:169,179-181``). Requires row/col extents padded
    to the tile grid; padding rows carry rowmul=0 so they contribute
    nothing.

    ``symmetric=True`` (default) exploits Gram symmetry: a folded
    triangular grid visits only upper block tiles — half the MXU FLOPs and
    half the HBM block fetches, a structural advantage a generic
    ``dot_general`` cannot express — then the result is mirrored with an
    elementwise triu + transpose. Requires an even feature-tile count;
    odd tile counts fall back to the full grid.
    """
    rows, n = x.shape
    if rows % block_r or n % block_n:
        raise ValueError(
            f"shape {(rows, n)} must be padded to multiples of "
            f"({block_r}, {block_n})"
        )
    from spark_rapids_ml_tpu.ops.covariance import default_gram_precision

    if precision is None:
        precision = default_gram_precision()
    n_tiles = n // block_n
    r_tiles = rows // block_r
    symmetric = symmetric and n_tiles % 2 == 0  # odd fold double-counts
    mean2d = mean.reshape(1, n).astype(x.dtype)
    rowmul2d = rowmul.reshape(rows, 1).astype(x.dtype)
    if symmetric:
        ij = _folded_triangle_maps(n_tiles)
        grid = (n_tiles // 2, n_tiles + 1, r_tiles)

        def _xi(p, q, r):
            return (r, ij(p, q)[0])

        def _xj(p, q, r):
            return (r, ij(p, q)[1])

        def _mi(p, q, r):
            return (0, ij(p, q)[0])

        def _mj(p, q, r):
            return (0, ij(p, q)[1])

        def _out(p, q, r):
            return ij(p, q)

    else:
        grid = (n_tiles, n_tiles, r_tiles)

        def _xi(i, j, r):
            return (r, i)

        def _xj(i, j, r):
            return (r, j)

        def _mi(i, j, r):
            return (0, i)

        def _mj(i, j, r):
            return (0, j)

        def _out(i, j, r):
            return (i, j)

    out = pl.pallas_call(
        _make_gram_kernel(precision, symmetric),
        out_shape=jax.ShapeDtypeStruct((n, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_n), _xi),
            pl.BlockSpec((block_r, block_n), _xj),
            pl.BlockSpec((1, block_n), _mi),
            pl.BlockSpec((1, block_n), _mj),
            pl.BlockSpec((block_r, 1), lambda *idx: (idx[-1], 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_n), _out),
        interpret=interpret,
        # 512×1024 blocks need ~17MB of scoped VMEM (see the block-size
        # comment above for the breakdown) — just past the 16MB default
        # scoped limit, well inside the chip's 128MB VMEM.
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(x, x, mean2d, mean2d, rowmul2d)
    if symmetric:
        # Diagonal block tiles are computed in full, so their strictly-lower
        # elements are already correct — the elementwise triu keeps one copy
        # and the transpose restores the mirrored half exactly. Lower tiles
        # the folded grid never visited are overwritten here, so their
        # (uninitialized) contents never escape.
        out = jnp.triu(out) + jnp.triu(out, 1).T
    return out


# the callers' name; the underscore is the program's name in every trace and
# in ``obs.compile_stats()``, which the benchmark's ledger and the smoke read
fused_centered_gram = _fused_centered_gram
