"""``PCA.fit`` has one body: whatever form the rows come in, they are a
``BatchSource`` walked by ``ops.streaming.stream_covariance`` (or its host
twin) and solved once. What an in-memory fit has by that construction —
the ingest counters, the chips of ``numDevices``, kept batches, one key set
of ``fit_timings_``, the solve's one tracked program — is pinned here,
against the float64 host oracle and not against another body.
"""

from __future__ import annotations

import jax
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.data.frame import VectorFrame
from spark_rapids_ml_tpu.ops import streaming

from conftest import numpy_pca_oracle

ROWS, N, K = 300, 12, 3
CHUNK = 77  # uneven against every batchRows below: re-blocked, masked tail


def _rows() -> np.ndarray:
    rng = np.random.default_rng(7)
    return rng.normal(size=(ROWS, N)) * np.linspace(0.5, 3.0, N) + 2.0


def _chunks(x: np.ndarray):
    return (x[i:i + CHUNK] for i in range(0, x.shape[0], CHUNK))


# name → (the dataset handed to fit, whether it can be walked twice)
FORMS = {
    "ndarray": (lambda x: x, True),
    "list_of_vectors": (lambda x: [row for row in x], True),
    "vector_frame": (lambda x: VectorFrame({"features": x}), True),
    "pandas": (lambda x: pd.DataFrame({"features": list(x)}), True),
    "callable": (lambda x: lambda: _chunks(x), True),
    "iterator": (lambda x: _chunks(x), False),
}


def _assert_oracle(model, x, mean_centering=True, atol=1e-8):
    pc, evr, mean = numpy_pca_oracle(x, K, mean_centering=mean_centering)
    np.testing.assert_allclose(model.pc, pc, atol=atol)
    np.testing.assert_allclose(model.explained_variance, evr, atol=atol)
    np.testing.assert_allclose(model.mean, mean, atol=atol)


@pytest.mark.parametrize("mean_centering", [True, False],
                         ids=["centred", "raw"])
@pytest.mark.parametrize("form", list(FORMS))
def test_every_input_form_is_the_stream(form, mean_centering):
    x = _rows()
    make, reiterable = FORMS[form]
    model = (PCA().setK(K).setMeanCentering(mean_centering).setBatchRows(64)
             .fit(make(x)))
    _assert_oracle(model, x, mean_centering)
    ingest = model.fit_report_.extra["ingest"]
    # mean pass then centred Gram where the rows can be walked twice and
    # are centred; the one-pass sufficient statistics otherwise
    assert ingest["passes"] == (2 if mean_centering and reiterable else 1)
    assert ingest["batches"] == ingest["passes"] * -(-ROWS // 64)
    assert model.fit_report_.rows == ROWS


@pytest.mark.parametrize("batch_rows", [0, 7, ROWS, 4 * ROWS])
def test_in_memory_fit_is_invariant_to_batch_rows(batch_rows):
    x = _rows()
    model = PCA().setK(K).setBatchRows(batch_rows).fit(x)
    reference = PCA().setK(K).setBatchRows(64).fit(x)
    np.testing.assert_allclose(model.pc, reference.pc, atol=1e-10)
    np.testing.assert_allclose(model.explained_variance,
                               reference.explained_variance, atol=1e-10)
    np.testing.assert_allclose(model.mean, reference.mean, atol=1e-10)
    ingest = model.fit_report_.extra["ingest"]
    if batch_rows != 7:
        # a matrix under one batch is one batch of exactly its rows: the
        # source clamps, nothing is padded — and one batch is its own mean,
        # so the Gram of pass 1 stands and the rows cross once
        assert ingest["batches"] == 1 and ingest["rows_put"] == ROWS
        assert ingest["gram_shift"]["accepted"] and ingest["passes"] == 1


def test_num_devices_is_honoured_for_a_matrix():
    x = _rows()
    model = PCA().setK(K).setNumDevices(2).setBatchRows(64).fit(x)
    _assert_oracle(model, x)
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["chips"] == 2
    devices = [str(d) for d in jax.local_devices()[:2]]
    assert [chip["device"] for chip in ingest["per_chip"]] == devices
    # five batches dealt in turn: 64 + 64 + 44 rows and 64 + 64
    assert [chip["rows"] for chip in ingest["per_chip"]] == [172, 128]
    assert set(ingest["collective_bytes"]) == {"mean", "gram"}
    assert "covariance/collective" in model.fit_timings_


def test_fit_timings_have_one_key_set_for_every_form():
    x = _rows()
    in_memory = PCA().setK(K).fit(x).fit_timings_
    streamed = PCA().setK(K).fit(lambda: _chunks(x)).fit_timings_
    # only a form that has to be made a matrix first has the phase for it
    assert set(in_memory) - {"densify"} == set(streamed)
    assert {"covariance", "covariance/next", "covariance/put",
            "covariance/dispatch", "covariance/sync", "solve",
            "fetch"} <= set(streamed)


@pytest.mark.parametrize("use_xla_svd", [True, False],
                         ids=["xla_svd", "host_svd"])
@pytest.mark.parametrize("use_xla_dot", [True, False],
                         ids=["xla_dot", "host_dot"])
def test_each_stage_records_where_it_ran(use_xla_dot, use_xla_svd):
    x = _rows()
    model = (PCA().setK(K).setUseXlaDot(use_xla_dot)
             .setUseXlaSvd(use_xla_svd).fit(x))
    _assert_oracle(model, x)
    extra = model.fit_report_.extra
    assert ("ingest" in extra) == use_xla_dot
    assert ("covariance/put" in model.fit_timings_) == use_xla_dot
    if use_xla_svd:
        assert model.svd_solver_used_ == "eigh"  # auto, k not ≪ n
        assert extra["solve"] == {"solver": "eigh", "gate": "ungated",
                                  "residual_ratio": None, "programs": 1}
    else:
        assert model.svd_solver_used_ is None  # host LAPACK
        assert "solve" not in extra
    assert {"densify", "covariance", "solve", "fetch"} <= set(
        model.fit_timings_)


@pytest.mark.parametrize("solver", ["eigh", "randomized"])
def test_an_explicit_solver_in_memory_is_the_one_tracked_program(solver):
    """An explicit ``svdSolver`` on a matrix used to inline the solve in a
    whole-fit program; it is the gated solve's one program now, noted."""
    rng = np.random.default_rng(3)
    # a decaying spectrum, so the randomized solve's gate passes
    x = rng.normal(size=(400, 32)) * (0.6 ** np.arange(32))
    model = PCA().setK(K).setSvdSolver(solver).fit(x)
    solve = model.fit_report_.extra["solve"]
    assert model.svd_solver_used_ == solve["solver"] == solver
    assert solve["programs"] == 1
    assert solve["gate"] == ("passed" if solver == "randomized"
                             else "ungated")
    pc, _, _ = numpy_pca_oracle(x, K)
    np.testing.assert_allclose(np.abs(model.pc), np.abs(pc), atol=1e-6)


def test_matrix_rows_that_fit_the_chip_cross_once(monkeypatch):
    """The CPU reports no device memory, so a budget stands in for the
    chip's (``tests/test_streaming_keep.py``): pass 1's batches of a
    matrix are kept and pass 2 puts nothing."""
    monkeypatch.setattr(streaming, "keep_budget_bytes",
                        lambda device, batch_nbytes, gram_nbytes: 1 << 40)
    x = _rows()
    model = PCA().setK(K).setBatchRows(64).fit(x)
    _assert_oracle(model, x)
    ingest = model.fit_report_.extra["ingest"]
    assert ingest["passes"] == 2
    assert ingest["batches"] == ingest["batches_kept"] == 5
    assert ingest["rows_put"] == 5 * 64  # once, the tail's padding included


@pytest.mark.parametrize("form", ["ndarray", "callable"])
def test_the_checks_come_once_for_every_form(form):
    x = _rows()
    make, _ = FORMS[form]
    with pytest.raises(ValueError, match="at most the number of features"):
        PCA().setK(N + 1).fit(make(x))
    with pytest.raises(ValueError, match="more than one row"):
        PCA().setK(1).fit(make(x[:1]))
    # one row is enough where nothing is centred
    assert PCA().setK(1).setMeanCentering(False).fit(make(x[:1])).pc.shape == (
        N, 1)
