"""Dense math kernels — the paddle/math Matrix::mul / hl_matrix_mul analog.

Reference: paddle/math/Matrix.cpp:502-536 (GpuMatrix::mul → cublasSgemm via
cuda/src/hl_cuda_cublas.cc:225). On TPU the gemm is ``jnp.dot`` lowered to the
MXU; the framework-wide policy is bfloat16 inputs with float32 accumulation
(``preferred_element_type``), which is both faster and the TPU-idiomatic
equivalent of the reference's float32 SGEMM.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.platform.flags import FLAGS


def compute_dtype(x: jax.Array) -> jnp.dtype:
    """Matmul/conv INPUT dtype under the global policy (bf16 when
    FLAGS.use_bf16; accumulation stays f32 via preferred_element_type)."""
    if FLAGS.use_bf16 and x.dtype in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return jnp.dtype(jnp.bfloat16)
    return x.dtype


def matmul(a: jax.Array, b: jax.Array, *, trans_a: bool = False,
           trans_b: bool = False, out_dtype=jnp.float32) -> jax.Array:
    """MXU matmul with bf16 inputs / f32 accumulation under the global policy."""
    if trans_a:
        a = jnp.swapaxes(a, -1, -2)
    if trans_b:
        b = jnp.swapaxes(b, -1, -2)
    ct = compute_dtype(a)
    return jnp.matmul(a.astype(ct), b.astype(ct),
                      preferred_element_type=jnp.dtype(out_dtype))


def dense_activation_dtype() -> jnp.dtype:
    """Storage dtype for dense/sequence layer outputs (fc, embedding,
    attention — the transformer residual stream). The dense analog of
    ops/conv.py activation_dtype: bf16 halves residual-stream HBM traffic;
    norm statistics and losses still reduce in f32 (ops/norm.py layer_norm,
    ops/losses.py softmax_cross_entropy upcast internally)."""
    if FLAGS.use_bf16 and FLAGS.bf16_dense_activations:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def fc(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """y = x @ w (+ b) — FullyConnectedLayer::forward analog
    (reference: gserver/layers/FullyConnectedLayer.cpp:69-88)."""
    y = matmul(x, w)
    if b is not None:
        y = y + b
    return y


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """Gated feed-forward ``(silu(x Wg) * (x Wu)) Wd`` (no biases), each
    product under the global matmul policy, the gate in f32."""
    return matmul(jax.nn.silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


def outer_product_update(x, y):
    """Rank-1 accumulate helper (reference Matrix::mul with trans variants)."""
    return matmul(x, y, trans_a=True)


def dropout(x: jax.Array, rate: float, key: jax.Array, train: bool) -> jax.Array:
    """Inverted dropout (reference: dropout in ExtraLayerAttribute/Layer.cpp)."""
    if not train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)
