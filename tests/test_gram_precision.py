"""The ``gramPrecision`` Param: the documented accuracy/speed trade.

VERDICT r4 #5: the 0.92-MFU single-pass bf16 Gram arm
(``records/r04/gram_sweep.json``) graduates from an env-var easter egg
(``TPUML_GRAM_PRECISION``) to a first-class Param with an accuracy
contract. CPU lanes prove the plumbing (param → kernel static args →
every fit path); the live-chip lane (``TPUML_CHIP_PRECISION=1``, quiet
chip) proves the numeric contract on real MXU hardware, where bf16
precision hints actually change the arithmetic.
"""

import os

import numpy as np
import pytest

from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.ops.covariance import resolve_gram_precision


def _oracle(x, k):
    xc = x - x.mean(axis=0)
    cov = xc.T @ xc / (x.shape[0] - 1)
    evals, evecs = np.linalg.eigh(cov)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    idx = np.argmax(np.abs(evecs), axis=0)
    evecs = evecs * np.where(
        evecs[idx, np.arange(evecs.shape[1])] < 0, -1.0, 1.0
    )[None, :]
    return evecs[:, :k], evals[:k] / evals.sum()


def _ill_conditioned(rng, n=2048, d=128, decay=0.92):
    """Power-law spectrum + large common mean: the regime where one-pass
    bf16 cancellation error is visible on real hardware."""
    scales = decay ** np.arange(d)
    return 100.0 + rng.normal(size=(n, d)) * scales[None, :]


def test_resolve_gram_precision_contract():
    assert resolve_gram_precision(None) == "bfloat16_3x"
    assert resolve_gram_precision("auto") == "bfloat16_3x"
    assert resolve_gram_precision("bfloat16") == "bfloat16"
    assert resolve_gram_precision("highest") == "highest"
    with pytest.raises(ValueError, match="gramPrecision"):
        resolve_gram_precision("fp8")


def test_param_validation_and_default():
    est = PCA()
    assert est.get_or_default("gramPrecision") == "auto"
    est.set("gramPrecision", "bfloat16")
    assert est.get_or_default("gramPrecision") == "bfloat16"
    with pytest.raises(ValueError):
        est.set("gramPrecision", "float16")


def test_env_var_still_respected_under_auto(monkeypatch):
    monkeypatch.setenv("TPUML_GRAM_PRECISION", "highest")
    assert resolve_gram_precision("auto") == "highest"
    # explicit param value wins over the env var
    assert resolve_gram_precision("bfloat16") == "bfloat16"


@pytest.mark.parametrize("precision", ["auto", "bfloat16", "bfloat16_3x",
                                       "float32", "highest"])
def test_every_precision_fits_and_matches_oracle_on_cpu(rng, precision):
    # CPU matmuls ignore MXU precision hints, so every arm must hit the
    # 1e-5 oracle bar here — this proves the PLUMBING (param accepted,
    # threaded to the kernels as a static arg, all paths compile)
    x = rng.normal(size=(512, 48))
    pc_exp, evr_exp = _oracle(x, 4)
    model = (PCA().setK(4).setInputCol("features")
             .set("gramPrecision", precision).fit(x))
    np.testing.assert_allclose(np.abs(model.pc), np.abs(pc_exp),
                               atol=1e-5)
    np.testing.assert_allclose(model.explained_variance, evr_exp,
                               atol=1e-5)


@pytest.mark.parametrize("shift", ["refused", "accepted"])
def test_precision_reaches_every_accumulate_step(rng, monkeypatch, shift):
    """The Param is the static ``precision`` of each Gram step of the
    fit's stream, whole batches and the masked tail alike: the steps of
    pass 1 (about the first batch's mean) and, where the rows refuse that
    shift (sorted by a feature here; mirrored pairs accept it), pass 2's."""
    from shift_rows import mirrored_pairs, verdict

    from spark_rapids_ml_tpu.ops import streaming

    seen = []
    real = streaming.update_centered_gram_auto

    def recording(gram_acc, batch, mean, mask=None, precision=None):
        seen.append((mask is not None, precision))
        return real(gram_acc, batch, mean, mask, precision=precision)

    monkeypatch.setattr(streaming, "update_centered_gram_auto", recording)
    x = rng.normal(size=(1000, 32))
    x = (x[np.argsort(x[:, 0])] if shift == "refused"
         else mirrored_pairs([x], 0.0)[0])
    pc_exp, evr_exp = _oracle(x, 3)
    model = (PCA().setK(3).setInputCol("features")
             .set("gramPrecision", "bfloat16").set("batchRows", 256).fit(x))
    passes = 2 if shift == "refused" else 1
    assert verdict(model.fit_report_.extra["ingest"]) == (
        shift == "accepted", passes)
    assert seen == ([(False, "bfloat16")] * 3 + [(True, "bfloat16")]) * passes
    np.testing.assert_allclose(np.abs(model.pc), np.abs(pc_exp), atol=1e-5)


def test_param_persists_and_roundtrips(rng, tmp_path):
    est = (PCA().setK(2).setInputCol("features")
           .set("gramPrecision", "bfloat16"))
    path = str(tmp_path / "est")
    est.save(path)
    loaded = PCA.load(path)
    assert loaded.get_or_default("gramPrecision") == "bfloat16"
    x = rng.normal(size=(64, 8))
    model = loaded.fit(x)
    assert model.get_or_default("gramPrecision") == "bfloat16"


@pytest.fixture
def rng():
    return np.random.default_rng(7)


# -- live-chip accuracy contract (opt-in: claims the accelerator) ---------

@pytest.mark.skipif(
    os.environ.get("TPUML_CHIP_PRECISION") != "1",
    reason="live accelerator precision contract "
           "(set TPUML_CHIP_PRECISION=1, run on a quiet chip)",
)
def test_chip_precision_contract():
    """On real MXU hardware: bfloat16_3x is oracle-grade; single-pass
    bfloat16 is measurably coarser but within its documented ~1e-2
    relative bound on ill-conditioned data — and measurably DIFFERENT
    from highest, proving the knob reaches the hardware."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.covariance import covariance

    rng = np.random.default_rng(3)
    x = _ill_conditioned(rng)
    xd = jnp.asarray(x, dtype=jnp.float32)
    cov_ref = np.cov(x, rowvar=False)
    scale = float(np.abs(cov_ref).max())

    cov_hi = np.asarray(covariance(xd, mean=jnp.mean(xd, axis=0),
                                   precision="highest"))
    cov_3x = np.asarray(covariance(xd, mean=jnp.mean(xd, axis=0),
                                   precision="bfloat16_3x"))
    cov_bf = np.asarray(covariance(xd, mean=jnp.mean(xd, axis=0),
                                   precision="bfloat16"))

    err_3x = np.abs(cov_3x - cov_ref).max() / scale
    err_bf = np.abs(cov_bf - cov_ref).max() / scale
    # the documented contract rows
    assert err_3x < 1e-4, f"bfloat16_3x rel err {err_3x}"
    assert err_bf < 1e-2, f"bfloat16 rel err {err_bf}"
    # the knob demonstrably reaches the MXU: single-pass differs from
    # the full-precision arm by more than float32 round-off
    assert np.abs(cov_bf - cov_hi).max() / scale > 1e-7
