"""Streaming accumulation must equal one-shot fit (batch-size invariance)."""

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.streaming import StreamingPCA, init_stats, update_stats

from conftest import numpy_pca_oracle

ABS_TOL = 1e-5


def test_streaming_matches_oracle(rng):
    x = rng.normal(loc=1.5, size=(300, 10))
    s = StreamingPCA(10, dtype=jnp.float64)
    for i in range(0, 300, 64):  # uneven final batch via mask padding
        batch = x[i : i + 64]
        pad = 64 - batch.shape[0]
        mask = np.ones(64)
        if pad:
            batch = np.concatenate([batch, np.zeros((pad, 10))])
            mask[64 - pad :] = 0.0
        s.partial_fit(jnp.asarray(batch), jnp.asarray(mask))
    assert s.rows_seen == 300
    res = s.finalize(4)
    pc, evr, mean = numpy_pca_oracle(x, 4)
    np.testing.assert_allclose(np.asarray(res.components), pc, atol=ABS_TOL)
    np.testing.assert_allclose(np.asarray(res.explained_variance), evr, atol=ABS_TOL)
    np.testing.assert_allclose(np.asarray(res.mean), mean, atol=ABS_TOL)


def test_batch_size_invariance(rng):
    x = rng.normal(size=(120, 6))
    results = []
    for bs in (8, 40, 120):
        s = StreamingPCA(6, dtype=jnp.float64)
        for i in range(0, 120, bs):
            s.partial_fit(jnp.asarray(x[i : i + bs]))
        results.append(np.asarray(s.finalize(3).components))
    np.testing.assert_allclose(results[0], results[1], atol=1e-10)
    np.testing.assert_allclose(results[0], results[2], atol=1e-10)


def test_no_mean_centering(rng):
    x = rng.normal(loc=3.0, size=(80, 5))
    s = StreamingPCA(5, dtype=jnp.float64)
    s.partial_fit(jnp.asarray(x))
    res = s.finalize(2, mean_centering=False)
    pc, evr, _ = numpy_pca_oracle(x, 2, mean_centering=False)
    np.testing.assert_allclose(np.asarray(res.components), pc, atol=ABS_TOL)
    np.testing.assert_allclose(np.asarray(res.mean), np.zeros(5), atol=0)


def test_donation_keeps_single_gram_buffer(rng):
    # update_stats donates: repeated updates must not error on reuse of the
    # donated buffers and count must accumulate exactly.
    stats = init_stats(4, dtype=jnp.float64)
    b = jnp.asarray(rng.normal(size=(16, 4)))
    for _ in range(5):
        stats = update_stats(stats, b)
    assert float(stats.count) == 80.0


# -- production Gram dispatch (update_stats_auto / accumulate_path) ---------


def _aligned_stats_and_batch(rng, rows=None, n=None, dtype=jnp.float32):
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_N, _BLOCK_R

    rows = rows if rows is not None else _BLOCK_R
    n = n if n is not None else 2 * _BLOCK_N
    stats = init_stats(n, dtype=dtype)
    batch = jnp.asarray(rng.normal(size=(rows, n)), dtype=dtype)
    return stats, batch


def test_fused_dispatch_rejects_cpu_and_auto_path_runs(rng):
    """On CPU the gate must pick the XLA path (Pallas doesn't lower) and
    update_stats_auto must still accumulate correctly through it."""
    from spark_rapids_ml_tpu.ops.streaming import (
        accumulate_path,
        update_stats_auto,
    )

    stats, batch = _aligned_stats_and_batch(rng)
    assert accumulate_path(stats.gram, batch, None) == "xla"
    out = update_stats_auto(stats, batch)
    assert int(out.count) == batch.shape[0]


def test_fused_dispatch_shape_branches(rng, monkeypatch):
    """Every rejection branch of the gate, with the platform check stubbed
    to 'tpu' so shape logic is what's under test (CPU CI otherwise
    short-circuits before reaching it)."""
    import spark_rapids_ml_tpu.ops.streaming as streaming
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_N, _BLOCK_R
    from spark_rapids_ml_tpu.ops.streaming import accumulate_path

    monkeypatch.setattr(streaming, "_gram_platform", lambda acc: "tpu")

    stats, batch = _aligned_stats_and_batch(rng)
    # aligned + f32 + tpu + no mask ⇒ fused
    assert accumulate_path(stats.gram, batch, None) == "pallas"

    # mask present ⇒ XLA
    mask = jnp.ones((batch.shape[0],))
    assert accumulate_path(stats.gram, batch, mask) == "xla"

    # misaligned rows ⇒ XLA (the fused step does not pad)
    assert accumulate_path(stats.gram, batch[: _BLOCK_R - 8], None) == "xla"

    # odd feature-tile count can't fold ⇒ XLA
    stats3, batch3 = _aligned_stats_and_batch(rng, n=3 * _BLOCK_N)
    assert accumulate_path(stats3.gram, batch3, None) == "xla"

    # non-f32 accumulator ⇒ XLA
    stats64, batch64 = _aligned_stats_and_batch(rng, dtype=jnp.float64)
    assert accumulate_path(stats64.gram, batch64, None) == "xla"

    # a traced accumulator has no device to ask ⇒ XLA
    seen = []
    jax.eval_shape(
        lambda g: seen.append(accumulate_path(g, batch, None)) or g,
        stats.gram)
    assert seen == ["xla"]


def test_gate_never_pads_a_width(monkeypatch):
    """The gate picks Pallas only at a width the folded grid takes as it
    is: in the bands between, padding to an even tile count would cost
    more than the XLA dot_general (the 784 cell's side of the choice)."""
    import spark_rapids_ml_tpu.ops.streaming as streaming
    from spark_rapids_ml_tpu.ops.pallas_gram import _BLOCK_N, _BLOCK_R
    from spark_rapids_ml_tpu.ops.streaming import accumulate_path

    monkeypatch.setattr(streaming, "_gram_platform", lambda acc: "tpu")
    block = 2 * _BLOCK_N

    def path(n):
        acc = jax.ShapeDtypeStruct((n, n), jnp.float32)
        batch = jax.ShapeDtypeStruct((_BLOCK_R, n), jnp.float32)
        return accumulate_path(acc, batch, None)

    assert path(4 * block) == "pallas"      # aligned: half the work
    assert path(block) == "pallas"          # aligned at one tile pair
    assert path(block + 76) == "xla"        # would pad to 2·block: 2× XLA
    assert path(784) == "xla"
    assert path(int(block * 1.45)) == "xla"


def test_centered_gram_auto_matches_plain(rng, monkeypatch):
    """update_centered_gram_auto must give the same result whichever kernel
    the gate picks (CPU here ⇒ XLA arm; the fused arm is covered by the
    interpret-mode pallas tests and the on-chip bench)."""
    from spark_rapids_ml_tpu.ops.streaming import (
        update_centered_gram,
        update_centered_gram_auto,
    )

    n = 16
    batch = jnp.asarray(rng.normal(size=(24, n)), dtype=jnp.float32)
    mean = jnp.asarray(rng.normal(size=(n,)), dtype=jnp.float32)
    a = update_centered_gram_auto(jnp.zeros((n, n), jnp.float32), batch, mean)
    b = update_centered_gram(jnp.zeros((n, n), jnp.float32), batch, mean)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
