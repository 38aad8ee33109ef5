"""The distributed model fits, end to end on a virtual device mesh.

Every fit below runs as a sharded XLA program over an 8-device mesh —
per-shard partial statistics combined by on-device collectives (psum /
all_gather), never a driver-side reduce. On real hardware the same code
spans TPU chips over ICI; here the mesh is 8 virtual CPU devices.

Run: ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python examples/distributed_models_example.py``
"""

import numpy as np


def main() -> None:
    from spark_rapids_ml_tpu.parallel import (
        data_mesh,
        distributed_dbscan_labels,
        distributed_gbt_fit,
        distributed_ivf_search,
        distributed_kneighbors,
        distributed_pca_fit,
        distributed_svc_fit,
        distributed_umap_optimize,
    )

    rng = np.random.default_rng(0)
    mesh = data_mesh()   # all visible devices — 8 virtual here, chips on a pod
    print(f"mesh: {mesh.devices.shape} devices, axes {mesh.axis_names}")

    x = rng.normal(size=(4096, 32))

    # PCA: per-shard (Gram, sum, count) partials, one fused psum
    pca = distributed_pca_fit(x, 4, mesh)
    print("PCA components:", np.asarray(pca.components).shape)

    # LinearSVC: one psum of active-set partials per Newton iteration
    y = (x[:, 0] - x[:, 1] > 0).astype(np.float64)
    svc = distributed_svc_fit(x, y, mesh, reg_param=0.01)
    print("LinearSVC coefficients:", np.asarray(svc.coefficients).shape)

    # GBT: per-level histogram psum per boosting iteration
    ens, edges, init, _gains = distributed_gbt_fit(
        x, y, mesh, max_iter=10, max_depth=3, classification=True
    )
    print("GBT ensemble:", ens.feature.shape)

    # exact KNN: per-shard top-k, all_gather, replicated merge
    d, i = distributed_kneighbors(
        x[:16].astype(np.float32), x.astype(np.float32), 5, mesh
    )
    print("KNN:", d.shape)

    # approximate KNN: inverted lists sharded, per-shard local probes
    from spark_rapids_ml_tpu import NearestNeighbors

    pq = (
        NearestNeighbors().setK(5).setAlgorithm("ivfpq")
        .setNlist(16).setNprobe(4).setRefineRatio(0)
        .fit(x.astype(np.float32))
    )
    dq, iq = distributed_ivf_search(pq, x[:16].astype(np.float32), mesh)
    print("IVF-PQ:", dq.shape)

    # DBSCAN: one epsilon-graph row panel per device, O(n) label exchange
    blobs = np.concatenate(
        [c + 0.3 * rng.normal(size=(300, 2))
         for c in [np.array([0, 8]), np.array([8, 0])]]
    )
    labels, core = distributed_dbscan_labels(blobs, 1.5, 5, mesh)
    print("DBSCAN clusters:", len(np.unique(labels[labels >= 0])))

    # UMAP: repulsion panels per device + psum of edge forces per epoch
    from spark_rapids_ml_tpu.ops.knn_kernel import knn_kernel
    from spark_rapids_ml_tpu.ops.umap_kernel import (
        fit_ab,
        pca_init,
        smooth_knn_calibration,
        symmetric_edge_list,
    )
    import jax.numpy as jnp

    xb = blobs.astype(np.float32)
    dists, idx = knn_kernel(jnp.asarray(xb), jnp.asarray(xb), 9)
    dists, idx = np.asarray(dists)[:, 1:], np.asarray(idx)[:, 1:]
    rho, sigma = smooth_knn_calibration(jnp.asarray(dists))
    mu = np.asarray(
        jnp.exp(-jnp.maximum(jnp.asarray(dists) - rho[:, None], 0.0)
                / sigma[:, None])
    )
    e_i, e_j, e_p = symmetric_edge_list(mu, idx, len(xb))
    a, b = fit_ab(0.1)
    emb = distributed_umap_optimize(
        e_i, e_j, e_p, np.asarray(pca_init(jnp.asarray(xb), 2)),
        mesh, a, b, repulsion_strength=0.1, n_epochs=50,
    )
    print("UMAP embedding:", emb.shape)

    # round-5 additions: hierarchical clustering, mixtures, smooth-
    # objective training, and NaiveBayes — all as sharded programs
    from spark_rapids_ml_tpu.parallel import (
        distributed_aft_fit,
        distributed_bisecting_kmeans_fit,
        distributed_fm_fit,
        distributed_gmm_fit,
        distributed_nb_fit,
    )

    bk = distributed_bisecting_kmeans_fit(blobs, 2, mesh, seed=1)
    print("BisectingKMeans leaves:", np.asarray(bk.centers).shape[0],
          "cost:", round(bk.cost, 2))

    gm = distributed_gmm_fit(blobs, 2, mesh, seed=1)
    print("GMM means:", np.round(np.asarray(gm.means), 1).tolist())

    y_fm = (blobs[:, 0] > 4).astype(float)
    fm_params, fm_iters, _ = distributed_fm_fit(
        blobs, y_fm, mesh, classification=True, factor_size=2,
        max_iter=100, step_size=0.05)
    print("FM trained:", fm_iters, "iters, factors",
          fm_params["factors"].shape)

    t = np.exp(0.2 * blobs[:, 0] + 1.0)
    aft_params, _i, _l = distributed_aft_fit(
        blobs, t, np.ones_like(t), mesh)
    print("AFT beta:", np.round(aft_params["beta"], 3).tolist())

    nb = distributed_nb_fit(np.abs(blobs), y_fm, mesh,
                            model_type="multinomial")
    print("NaiveBayes theta:", np.asarray(nb.theta).shape)


if __name__ == "__main__":
    main()
