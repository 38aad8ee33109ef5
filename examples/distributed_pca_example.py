"""Distributed PCA over a device mesh: per-device partial Gram, on-device
psum over ICI — replacing the reference's executor→driver serialization of
n×n partial covariances (``RapidsRowMatrix.scala:202``).

Runs anywhere: on a multi-chip TPU host it uses the real chips; elsewhere,
launch with a virtual 8-device CPU mesh:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/distributed_pca_example.py
"""

import jax
import numpy as np

from spark_rapids_ml_tpu.parallel.distributed_pca import distributed_pca_fit
from spark_rapids_ml_tpu.parallel.mesh import data_mesh

mesh = data_mesh()
print(f"devices: {jax.devices()}")
print(f"mesh: {dict(mesh.shape)}")

X = np.random.default_rng(3).normal(size=(8192, 256)).astype(np.float32)
result = distributed_pca_fit(X, k=8, mesh=mesh)

print("components:", np.asarray(result.components).shape)
print("explained variance ratio:", np.asarray(result.explained_variance)[:4])

# cross-check against the host oracle
Xc = X.astype(np.float64) - X.mean(axis=0)
cov = Xc.T @ Xc / (len(X) - 1)
w, v = np.linalg.eigh(cov)
top = v[:, np.argsort(w)[::-1][:8]]
err = np.abs(np.abs(np.asarray(result.components, np.float64)) - np.abs(top)).max()
print(f"|components - oracle| = {err:.2e}")
