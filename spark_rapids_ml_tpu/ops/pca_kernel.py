"""The single-device PCA projection programs, and the fit's result type.

``pca_transform_kernel`` enables the batched on-device transform the
reference declared but left disabled ("TODO(rongou): make this faster",
``RapidsPCA.scala:172-190``, native ``dgemm_1b`` at
``rapidsml_jni.cu:260-336``): here it is a single MXU matmul over the whole
batch.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.obs.xprof import tracked_jit
from spark_rapids_ml_tpu.ops.quantize import quantize_symmetric


class PCAFitResult(NamedTuple):
    components: jnp.ndarray          # (n_features, k), column j = j-th PC
    explained_variance: jnp.ndarray  # (k,) ratios λᵢ/Σλ
    mean: jnp.ndarray                # (n_features,) column means (or zeros)


def _project(x: jnp.ndarray, components: jnp.ndarray) -> jnp.ndarray:
    """The shared projection body: X @ PC — one MXU matmul.

    Spark PCA semantics: NO mean subtraction at transform time
    (``RapidsPCA.scala:187-189`` multiplies ``pc.transpose`` by the raw row
    vector), so we match that exactly for drop-in parity.
    """
    return lax.dot_general(
        x,
        components.astype(x.dtype),
        (((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST,
    )


@tracked_jit
def pca_transform_kernel(
    x: jnp.ndarray, components: jnp.ndarray
) -> jnp.ndarray:
    """Project a whole batch: X @ PC — one MXU matmul (see ``_project``)."""
    return _project(x, components)


# -- serving variants -------------------------------------------------------
# The pipelined micro-batcher's dispatch step calls these through
# ``PCAModel.serving_transform_program`` so batch N+1's transfer overlaps
# batch N's compute. The *_serve variant donates the staged input buffer:
# the pipeline stages a fresh device buffer per batch and never re-reads
# it, so XLA may retire/reuse its memory the moment the program consumes
# it (aliasing engages only where shape+dtype permit; elsewhere donation
# is a no-op — and the batcher's retry path always re-stages from host
# rows, so a donated buffer is never one a retry still holds). The
# reduced-precision variants are separate tracked signatures per bucket,
# env-gated by the engine (SPARK_RAPIDS_ML_TPU_SERVE_PRECISION) and
# guarded by its offline max-error check + the numerics sentinel; they
# skip donation because the cast consumes the input immediately.

pca_transform_serve = tracked_jit(
    _project, label="pca_transform_serve", donate_argnums=(0,)
)


def _project_bf16(x: jnp.ndarray,
                  components_bf16: jnp.ndarray) -> jnp.ndarray:
    """bf16 operands, f32 accumulation (``preferred_element_type``) —
    the documented reduced-precision GEMM posture of the gram sweep.
    The components arrive PRE-CAST (staged once at program build); only
    the per-batch operand casts here."""
    return lax.dot_general(
        x.astype(jnp.bfloat16), components_bf16, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


pca_transform_bf16 = tracked_jit(_project_bf16, label="pca_transform_bf16")


def _project_int8(x: jnp.ndarray, components_q: jnp.ndarray,
                  components_scale: jnp.ndarray) -> jnp.ndarray:
    """Per-tensor symmetric int8 GEMM with int32 accumulation, f32
    dequantized output (``ops.quantize``). The components arrive
    PRE-QUANTIZED (``quantize_symmetric_host`` at program build) — only
    the batch pays the max/round/clip reduction per call."""
    xq, sx = quantize_symmetric(x)
    acc = lax.dot_general(
        xq, components_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * (sx * components_scale)


pca_transform_int8 = tracked_jit(_project_int8, label="pca_transform_int8")


# Un-jitted stage bodies for the FUSED whole-pipeline serving programs
# (models._serving.build_fused_pipeline_program): the same arithmetic as
# the jitted serve kernels above, composed with the other stages inside
# ONE tracked_jit so a multi-stage PipelineModel predict is a single XLA
# dispatch. Keyed by precision exactly like the kernel tables.
SERVING_STAGE_BODIES = {
    "native": _project,
    "bf16": _project_bf16,
    "int8": _project_int8,
}
