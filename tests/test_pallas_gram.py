"""Fused Pallas Gram kernel vs the XLA covariance path (interpret mode on
CPU; the same kernel compiles for TPU tiles)."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops.covariance import covariance
from spark_rapids_ml_tpu.ops.pallas_gram import (
    _BLOCK_N,
    _BLOCK_R,
    fused_centered_gram,
)


def test_fused_matches_xla_exact_tiles(rng):
    x = rng.normal(size=(_BLOCK_R, _BLOCK_N)).astype(np.float32)
    mean = x.mean(axis=0)
    n = x.shape[0]
    rowmul = np.full(n, 1.0 / np.sqrt(n - 1), dtype=np.float32)
    got = fused_centered_gram(
        jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rowmul), interpret=True
    )
    want = covariance(jnp.asarray(x), mean=jnp.asarray(mean))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_fused_respects_row_mask(rng):
    """Rows whose rowmul is 0 contribute nothing, whatever they hold."""
    rows, n, valid = _BLOCK_R, 2 * _BLOCK_N, 500
    x = rng.normal(size=(rows, n)).astype(np.float32)
    x[valid:] = 1e6  # rows beyond 500 are garbage
    rowmul = np.zeros(rows, dtype=np.float32)
    rowmul[:valid] = 1.0
    mean = x[:valid].mean(axis=0)
    got = fused_centered_gram(
        jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rowmul),
        interpret=True, precision="highest",
    )
    xc = x[:valid].astype(np.float64) - mean.astype(np.float64)
    np.testing.assert_allclose(np.asarray(got), xc.T @ xc, atol=5e-3)


def test_unpadded_shape_rejected(rng):
    x = jnp.asarray(rng.normal(size=(100, 37)).astype(np.float32))
    with pytest.raises(ValueError, match="padded"):
        fused_centered_gram(x, jnp.zeros(37), jnp.ones(100), interpret=True)


def test_symmetric_matches_full_grid(rng):
    """The folded triangular grid must equal the full grid bit-for-bit in
    the mirrored upper triangle (same tile dots, same accumulation order
    over r) and stay exactly symmetric."""
    rows, n = 2 * _BLOCK_R, 2 * _BLOCK_N
    x = jnp.asarray(rng.normal(size=(rows, n)).astype(np.float32))
    mean = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    rowmul = jnp.asarray(rng.uniform(0.5, 1.5, size=(rows,)).astype(np.float32))
    full = np.asarray(
        fused_centered_gram(x, mean, rowmul, interpret=True, symmetric=False)
    )
    sym = np.asarray(
        fused_centered_gram(x, mean, rowmul, interpret=True, symmetric=True)
    )
    np.testing.assert_array_equal(sym, sym.T)
    np.testing.assert_allclose(sym, full, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("bn,br", [(256, 512), (128, 256)])
def test_custom_block_shapes_match(rng, bn, br):
    """Block-size parametrization (the r4 sweep arms): any tile-aligned
    (block_n, block_r) computes the identical folded-symmetric Gram."""
    n, rows = 1024, 2048  # tile-aligned for the default AND custom blocks
    x = rng.normal(size=(rows, n)).astype(np.float32)
    mean = rng.normal(size=n).astype(np.float32)
    rowmul = rng.uniform(0.5, 1.5, size=rows).astype(np.float32)
    ref = fused_centered_gram(
        jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rowmul),
        interpret=True, precision="highest",
    )
    out = fused_centered_gram(
        jnp.asarray(x), jnp.asarray(mean), jnp.asarray(rowmul),
        interpret=True, precision="highest", block_n=bn, block_r=br,
    )
    # different tilings accumulate in different orders: f32 rounding only
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-3
    )
