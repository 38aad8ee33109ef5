from spark_rapids_ml_tpu.ops.covariance import column_means, covariance, gram
from spark_rapids_ml_tpu.ops.eigh import (
    eigh_descending,
    pca_from_covariance,
    pca_from_covariance_gated,
    resolve_auto_solver,
    sign_flip,
)
from spark_rapids_ml_tpu.ops.pca_kernel import pca_transform_kernel

__all__ = [
    "column_means",
    "covariance",
    "gram",
    "eigh_descending",
    "sign_flip",
    "pca_from_covariance",
    "pca_from_covariance_gated",
    "resolve_auto_solver",
    "pca_transform_kernel",
]
