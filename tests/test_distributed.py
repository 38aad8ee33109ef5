"""Distributed fit on an 8-virtual-device CPU mesh vs the oracle.

The multi-device story the reference never had (SURVEY.md §4: its "2
partitions in one JVM" is the closest analogue). Validates: row sharding,
psum of partials, padding/masking of uneven row counts, one-pass vs
two-pass schedule agreement.
"""

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu.parallel import data_mesh, distributed_pca_fit
from spark_rapids_ml_tpu.parallel.mesh import grid_mesh, pad_rows_to_multiple

from conftest import numpy_pca_oracle

ABS_TOL = 1e-5


def test_eight_virtual_devices_available():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_distributed_matches_oracle(rng, n_dev):
    x = rng.normal(size=(200, 12))
    mesh = data_mesh(n_dev)
    res = distributed_pca_fit(x, 5, mesh)
    pc, evr, mean = numpy_pca_oracle(x, 5)
    np.testing.assert_allclose(np.asarray(res.components), pc, atol=ABS_TOL)
    np.testing.assert_allclose(
        np.asarray(res.explained_variance), evr, atol=ABS_TOL
    )
    np.testing.assert_allclose(np.asarray(res.mean), mean, atol=ABS_TOL)


def test_uneven_rows_padded_and_masked(rng):
    # 203 rows over 8 devices: padding must not perturb results.
    x = rng.normal(size=(203, 9))
    mesh = data_mesh(8)
    res = distributed_pca_fit(x, 4, mesh)
    pc, evr, _ = numpy_pca_oracle(x, 4)
    np.testing.assert_allclose(np.asarray(res.components), pc, atol=ABS_TOL)
    np.testing.assert_allclose(
        np.asarray(res.explained_variance), evr, atol=ABS_TOL
    )


def test_one_pass_matches_two_pass(rng):
    x = rng.normal(loc=5.0, size=(160, 10))  # nonzero mean stresses G−nμμᵀ
    mesh = data_mesh(8)
    r1 = distributed_pca_fit(x, 3, mesh, one_pass=True)
    r2 = distributed_pca_fit(x, 3, mesh, one_pass=False)
    np.testing.assert_allclose(
        np.asarray(r1.components), np.asarray(r2.components), atol=ABS_TOL
    )
    np.testing.assert_allclose(
        np.asarray(r1.explained_variance),
        np.asarray(r2.explained_variance),
        atol=ABS_TOL,
    )


@pytest.mark.parametrize("one_pass", [False, True])
def test_randomized_solver_inside_the_mesh_program(rng, one_pass):
    """solver='randomized' reaches the eigensolve under shard_map (the
    dense one compiles for minutes at n=4096 on a TPU) and agrees with
    eigh on a decaying spectrum, as in distributed_streaming_pca_fit."""
    d = 24
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    x = (rng.normal(size=(256, d)) @ (q * 2.0 ** (-np.arange(d)))).astype(
        np.float32
    )
    mesh = data_mesh(8)
    res_r = distributed_pca_fit(
        x, 4, mesh, one_pass=one_pass, solver="randomized")
    res_e = distributed_pca_fit(x, 4, mesh, one_pass=one_pass)
    np.testing.assert_allclose(
        np.asarray(res_r.components), np.asarray(res_e.components), atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(res_r.explained_variance),
        np.asarray(res_e.explained_variance),
        rtol=1e-3,
    )


def test_no_mean_centering_distributed(rng):
    x = rng.normal(loc=2.0, size=(96, 6))
    mesh = data_mesh(4)
    res = distributed_pca_fit(x, 2, mesh, mean_centering=False)
    pc, evr, _ = numpy_pca_oracle(x, 2, mean_centering=False)
    np.testing.assert_allclose(np.asarray(res.components), pc, atol=ABS_TOL)
    np.testing.assert_allclose(
        np.asarray(res.explained_variance), evr, atol=ABS_TOL
    )


def test_pad_rows_to_multiple():
    x = np.ones((5, 3))
    xp, mask = pad_rows_to_multiple(x, 4)
    assert xp.shape == (8, 3) and mask.sum() == 5
    xp2, mask2 = pad_rows_to_multiple(x, 5)
    assert xp2.shape == (5, 3) and mask2.sum() == 5


def test_mesh_validation():
    with pytest.raises(ValueError, match="devices"):
        data_mesh(99)
    with pytest.raises(ValueError, match="devices"):
        grid_mesh(8, 2)


def test_grid_mesh_shape():
    mesh = grid_mesh(4, 2)
    assert mesh.devices.shape == (4, 2)
    assert mesh.axis_names == ("data", "feature")


def test_distributed_bisecting_kmeans_blobs(rng):
    from spark_rapids_ml_tpu.parallel import (
        distributed_bisecting_kmeans_fit,
    )

    centers = np.asarray([[0.0, 0.0], [8.0, 8.0],
                          [-8.0, 8.0], [0.0, -9.0]])
    x = np.concatenate([c + rng.normal(scale=0.4, size=(40, 2))
                        for c in centers])
    mesh = data_mesh(8)
    res = distributed_bisecting_kmeans_fit(x, 4, mesh, seed=3)
    assert np.asarray(res.centers).shape == (4, 2)
    for g in range(4):
        assert len(set(res.labels[g * 40:(g + 1) * 40])) == 1
    assert res.cost > 0
    # matches the Spark-plane / local hierarchy semantics: every
    # recovered center sits on one true blob
    got = np.asarray(res.centers)
    for c in centers:
        assert np.abs(got - c[None, :]).sum(axis=1).min() < 0.5


def test_distributed_bisecting_kmeans_degenerate(rng):
    from spark_rapids_ml_tpu.parallel import (
        distributed_bisecting_kmeans_fit,
    )

    mesh = data_mesh(8)
    # identical points cannot be bisected: one leaf, no crash
    res = distributed_bisecting_kmeans_fit(
        np.ones((32, 3)), 4, mesh, seed=0)
    assert np.asarray(res.centers).shape[0] == 1
    assert set(res.labels) == {0}
    # uneven row count exercises the padding mask
    x = rng.normal(size=(67, 3))
    res2 = distributed_bisecting_kmeans_fit(x, 3, mesh, seed=1)
    assert res2.labels.shape == (67,)
    assert np.isfinite(np.asarray(res2.centers)).all()


def test_distributed_gmm_recovers_components(rng):
    from spark_rapids_ml_tpu.models.gaussian_mixture import (
        GaussianMixture,
    )
    from spark_rapids_ml_tpu.parallel import distributed_gmm_fit

    means_true = np.asarray([[0.0, 0.0], [6.0, 6.0], [-6.0, 6.0]])
    x = np.concatenate([m + rng.normal(scale=0.5, size=(60, 2))
                        for m in means_true])
    mesh = data_mesh(8)
    model = distributed_gmm_fit(x, 3, mesh, seed=2)
    got = np.asarray(model.means)
    for m in means_true:
        assert np.abs(got - m[None, :]).sum(axis=1).min() < 0.3
    # same driver loop as the local fit: component means agree
    local = GaussianMixture().setK(3).setSeed(2).fit(x)
    lg = np.asarray(local.means)
    for m in got:
        assert np.abs(lg - m[None, :]).sum(axis=1).min() < 0.2
    # model surface intact (same class every path produces)
    assert abs(float(np.asarray(model.weights).sum()) - 1.0) < 1e-9
    assert model.num_iterations_ >= 1


def test_distributed_gmm_weighted_uneven(rng):
    from spark_rapids_ml_tpu.parallel import distributed_gmm_fit

    mesh = data_mesh(8)
    x = np.concatenate([rng.normal(0, 0.5, size=(50, 3)),
                        rng.normal(5, 0.5, size=(51, 3))])
    w = np.linspace(0.5, 2.0, 101)
    model = distributed_gmm_fit(x, 2, mesh, seed=1, weights=w)
    assert np.asarray(model.means).shape == (2, 3)
    assert np.isfinite(np.asarray(model.covs)).all()


def test_distributed_fm_fit(rng):
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.fm import fm_raw
    from spark_rapids_ml_tpu.parallel import distributed_fm_fit

    mesh = data_mesh(8)
    x = rng.normal(size=(400, 6))
    y = x @ [1.5, -1.0, 0.2, 0.0, 0.0, 0.5]
    params, n_iter, loss = distributed_fm_fit(
        x, y, mesh, factor_size=2, max_iter=200, step_size=0.05, seed=0)
    pred = np.asarray(fm_raw(
        {k: jnp.asarray(v, dtype=jnp.float32)
         for k, v in params.items()},
        jnp.asarray(x, dtype=jnp.float32)))
    assert np.corrcoef(pred, y)[0, 1] > 0.99
    assert n_iter >= 1 and np.isfinite(loss)

    yb = (y > 0).astype(float)
    pc, _it, _l = distributed_fm_fit(
        x, yb, mesh, classification=True, factor_size=2, max_iter=200,
        step_size=0.05, seed=0)
    pred2 = np.asarray(fm_raw(
        {k: jnp.asarray(v, dtype=jnp.float32) for k, v in pc.items()},
        jnp.asarray(x, dtype=jnp.float32)))
    assert ((pred2 > 0) == yb).mean() > 0.95


def test_distributed_aft_matches_local(rng):
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models.survival_regression import (
        AFTSurvivalRegression,
    )
    from spark_rapids_ml_tpu.parallel import distributed_aft_fit

    mesh = data_mesh(8)
    x = rng.normal(size=(300, 4))
    t = np.exp(x @ [0.5, -0.3, 0.1, 0.0] + 1.0)
    cens = (rng.random(300) > 0.2).astype(float)
    params, n_iter, _loss = distributed_aft_fit(
        x, t, cens, mesh, max_iter=100)
    local = AFTSurvivalRegression().fit(VectorFrame({
        "features": x, "label": t.tolist(), "censor": cens.tolist()}))
    # the mesh objective is EXACTLY the local objective (global
    # weighted mean via psum), so coefficients agree to f32 tolerance
    np.testing.assert_allclose(
        params["beta"], np.asarray(local.coefficients), atol=5e-2)
    assert abs(float(params["intercept"])
               - float(local.intercept)) < 5e-2
    # uneven rows exercise the zero-weight padding
    p2, _i, _l = distributed_aft_fit(x[:173], t[:173], cens[:173],
                                     mesh, max_iter=20)
    assert np.isfinite(p2["beta"]).all()


def test_distributed_naive_bayes_matches_local(rng):
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models.naive_bayes import NaiveBayes
    from spark_rapids_ml_tpu.parallel import distributed_nb_fit

    mesh = data_mesh(8)
    y = rng.integers(0, 3, size=301).astype(float)  # uneven rows
    for kind in ("multinomial", "gaussian", "bernoulli", "complement"):
        if kind == "bernoulli":
            x = (rng.random(size=(301, 10)) > 0.6).astype(float)
        elif kind == "gaussian":
            x = rng.normal(size=(301, 10))
        else:
            x = rng.poisson(2.0, size=(301, 10)).astype(float)
        dm = distributed_nb_fit(x, y, mesh, model_type=kind)
        local = NaiveBayes().setModelType(kind).fit(x, labels=y)
        np.testing.assert_allclose(dm.pi, local.pi, atol=1e-5)
        np.testing.assert_allclose(dm.theta, local.theta, atol=1e-4)
        if kind == "gaussian":
            np.testing.assert_allclose(dm.sigma, local.sigma, atol=1e-4)

    # weightCol semantics match the local weighted fit
    w = rng.uniform(0.5, 2.0, size=301)
    x = rng.poisson(2.0, size=(301, 10)).astype(float)
    dm = distributed_nb_fit(x, y, mesh, weights=w)
    frame = VectorFrame({"features": x, "label": y.tolist(),
                         "wt": w.tolist()})
    local = NaiveBayes().setWeightCol("wt").fit(frame)
    np.testing.assert_allclose(dm.theta, local.theta, atol=1e-4)


def test_distributed_pic_matches_local(rng):
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models.pic import PowerIterationClustering
    from spark_rapids_ml_tpu.parallel import distributed_pic_assign

    mesh = data_mesh(8)
    # two triangles: unambiguous 2-way split
    src = [0, 1, 0, 3, 4, 3]
    dst = [1, 2, 2, 4, 5, 5]
    ids, labels = distributed_pic_assign(src, dst, k=2, mesh=mesh,
                                         max_iter=20, seed=1)
    got = dict(zip(ids.tolist(), labels.tolist()))
    assert got[0] == got[1] == got[2] != got[3] == got[4] == got[5]

    # a larger multi-community graph: the mesh form must produce the
    # SAME partition as the local PIC (same affinity builder, same
    # iteration, same seeding) — row-sharding changes memory, not math
    src2, dst2 = [], []
    for c in range(3):
        base = c * 40
        for i in range(40):
            src2.append(base + i)
            dst2.append(base + (i + 1) % 40)
            src2.append(base + i)
            dst2.append(base + (i + 7) % 40)
    ids2, l2 = distributed_pic_assign(src2, dst2, k=3, mesh=mesh,
                                      max_iter=30, seed=4)
    local = (PowerIterationClustering().set("k", 3)
             .set("maxIter", 30).set("seed", 4))
    out = local.assign_clusters(VectorFrame({
        "src": [float(s) for s in src2],
        "dst": [float(d) for d in dst2]}))
    ll = np.asarray(out.column("cluster"))
    # the sharded matvec sums in a different fp order than the local
    # one, so near-tie k-means draws may flip a boundary point: require
    # co-membership agreement on >=95% of sampled pairs, not all
    pairs = [(i, j) for i in range(0, 120, 7)
             for j in range(0, 120, 11)]
    agree = sum((l2[i] == l2[j]) == (ll[i] == ll[j])
                for i, j in pairs)
    assert agree / len(pairs) >= 0.95


def test_distributed_mlp_fit(rng):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.mlp_kernel import forward_logits
    from spark_rapids_ml_tpu.parallel import distributed_mlp_fit

    mesh = data_mesh(8)
    centers = np.asarray([[0, 0, 0, 0], [4, 4, 0, 0], [0, 4, 4, 0]],
                         dtype=np.float64)
    y = rng.integers(0, 3, size=301).astype(float)  # uneven rows
    x = rng.normal(size=(301, 4)) + centers[y.astype(int)]
    params, n_iter, loss = distributed_mlp_fit(
        x, y, [4, 8, 3], mesh, max_iter=200, seed=1)
    logits = np.asarray(forward_logits(
        jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params),
        jnp.asarray(x, jnp.float32)))
    assert (logits.argmax(axis=1) == y).mean() > 0.9
    assert n_iter >= 1 and np.isfinite(loss)
    with pytest.raises(ValueError, match="class indices"):
        distributed_mlp_fit(x, y + 0.5, [4, 8, 3], mesh)


def test_distributed_glm_matches_local(rng):
    from spark_rapids_ml_tpu.data.frame import VectorFrame
    from spark_rapids_ml_tpu.models.glm import (
        GeneralizedLinearRegression,
    )
    from spark_rapids_ml_tpu.parallel import distributed_glm_fit

    mesh = data_mesh(8)
    x = rng.normal(size=(301, 4))  # uneven rows exercise padding

    lam = np.exp(x @ [0.5, -0.3, 0.2, 0.0] + 1.0)
    y = rng.poisson(lam).astype(float)
    m = distributed_glm_fit(x, y, mesh, family="poisson")
    local = GeneralizedLinearRegression().set("family", "poisson").fit(
        VectorFrame({"features": x, "label": y.tolist()}))
    np.testing.assert_allclose(np.asarray(m.coefficients),
                               np.asarray(local.coefficients),
                               atol=2e-3)
    assert abs(float(m.intercept) - float(local.intercept)) < 2e-3

    # binomial with weights + offset: the full statistics surface
    p_ = 1.0 / (1.0 + np.exp(-(x @ [1.0, -1.0, 0.0, 0.5])))
    yb = (rng.random(301) < p_).astype(float)
    w = rng.uniform(0.5, 2.0, size=301)
    off = rng.normal(scale=0.1, size=301)
    mb = distributed_glm_fit(x, yb, mesh, family="binomial",
                             weights=w, offset=off)
    localb = (GeneralizedLinearRegression().set("family", "binomial")
              .set("weightCol", "wt").set("offsetCol", "off")
              .fit(VectorFrame({"features": x, "label": yb.tolist(),
                                "wt": w.tolist(),
                                "off": off.tolist()})))
    np.testing.assert_allclose(np.asarray(mb.coefficients),
                               np.asarray(localb.coefficients),
                               atol=5e-3)

    # domain validation still fires at the mesh layer
    with pytest.raises(ValueError):
        distributed_glm_fit(x, y - 100.0, mesh, family="poisson")


def test_distributed_word2vec_cluster_recovery(rng):
    """Same oracle as the local Word2Vec tests: two disjoint
    co-occurrence clusters must land closer (cosine) within than
    across. The mesh step is the local update rule computed over the
    union of shards (psum'd gradient/count tables), so the established
    corpus/hyperparameters transfer directly."""
    from spark_rapids_ml_tpu.parallel import distributed_word2vec_fit

    a_words = ["apple", "banana", "cherry", "date", "elder"]
    b_words = ["wrench", "hammer", "pliers", "drill", "saw"]
    sents = []
    for i in range(300):
        words = a_words if i % 2 == 0 else b_words
        sents.append(list(rng.choice(words, size=8)))
    mesh = data_mesh(8)
    model = distributed_word2vec_fit(
        sents, mesh, vector_size=16, window=3, min_count=1,
        max_iter=20, batch_size=512, step_size=0.2, seed=7)
    syn = model.find_synonyms("apple", 4)
    assert set(syn.column("word")) == set(a_words) - {"apple"}
    all_syn = model.find_synonyms("apple", 9)
    assert set(list(all_syn.column("word"))[:4]) \
        == set(a_words) - {"apple"}
    assert model.num_pairs_ > 0 and np.isfinite(model.final_loss_)
