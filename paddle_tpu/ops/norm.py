"""Normalization kernels — BatchNorm/CrossMapNorm analogs.

Reference: paddle/gserver/layers/BatchNormalizationLayer.cpp,
CudnnBatchNormLayer.cpp (moving mean/var, use_global_stats),
CMRProjectionNormLayer + paddle/function/CrossMapNormalOp.cpp (LRN),
SumToOneNormLayer, RowL2NormLayer; Gen-2 paddle/operators/batch_norm_op.cc.

Batch norm is functional: ``batch_norm`` returns (y, new_moving_mean,
new_moving_var) in train mode so the trainer threads running statistics through
its state pytree — the TPU-native replacement for in-place moving buffers.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def batch_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               moving_mean: jax.Array, moving_var: jax.Array, *,
               train: bool, momentum: float = 0.9, eps: float = 1e-5,
               use_global_stats: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Normalize over all axes but the last (channel) axis.

    Works for [N, C] and [N, H, W, C]. Returns (y, new_mean, new_var).
    """
    reduce_axes = tuple(range(x.ndim - 1))
    use_batch_stats = train and not (use_global_stats or False)
    n = x.size // x.shape[-1]
    if use_batch_stats:
        # stats in f32 (bf16 mean/var over N*H*W elements loses too many
        # mantissa bits), via ONE fused pass: both sums are a multi-output
        # reduction XLA fuses into a single read of x, where the
        # mean-then-squared-deviation formulation costs two passes — for a
        # bandwidth-bound BN that second read is the dominant cost. The
        # sums are taken about the per-channel moving mean as a pilot so
        # E[d^2]-E[d]^2 subtracts small quantities even when |mean| >> std
        # (the raw-moment form cancels catastrophically there).
        pilot = jax.lax.stop_gradient(moving_mean).astype(jnp.float32)
        d = x.astype(jnp.float32) - pilot
        s1 = jnp.sum(d, axis=reduce_axes)
        s2 = jnp.sum(jnp.square(d), axis=reduce_axes)
        mean = pilot + s1 / n
        var = jnp.maximum(s2 / n - jnp.square(s1 / n), 0.0)
        unbiased = var * (n / max(1, n - 1))
        new_mean = momentum * moving_mean + (1.0 - momentum) * mean
        new_var = momentum * moving_var + (1.0 - momentum) * unbiased
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = jax.lax.rsqrt(var + eps)
    # fold the whole affine into per-channel scale/bias kept in f32 (the
    # folded bias can be large relative to the normalized signal, so
    # rounding it to bf16 before use adds error); only the final y is cast
    # to the activation dtype — HBM traffic is the bf16 read of x and
    # write of y either way, and XLA fuses the f32 elementwise middle
    scale = inv * gamma.astype(jnp.float32)
    bias = beta.astype(jnp.float32) - mean * scale
    y = (x.astype(jnp.float32) * scale + bias).astype(x.dtype)
    return y, new_mean, new_var


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """Row-stat normalization; like batch_norm above, statistics always
    reduce in f32 (bf16 residual streams exist under
    FLAGS.bf16_dense_activations), output in the input dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * gamma
            + beta).astype(x.dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Root-mean-square normalization with a learned gain, no mean and no
    bias: ``x * rsqrt(mean(x^2) + eps) * gamma``.  Statistics reduce in
    f32 as in ``layer_norm``; output in the input dtype."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * gamma).astype(x.dtype)


def cross_map_norm(x: jax.Array, size: int = 5, scale: float = 1e-4,
                   power: float = 0.75) -> jax.Array:
    """Local response normalization across channels (reference:
    function/CrossMapNormalOp.cpp). x: [N,H,W,C]."""
    # denominator in f32: bf16 activations would make the window-summed
    # squares (and the pow) lossy; cast back to the input dtype at the end
    sq = jnp.square(x.astype(jnp.float32))
    half = size // 2
    padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, size - 1 - half)))
    acc = jax.lax.reduce_window(padded, 0.0, jax.lax.add,
                                (1, 1, 1, size), (1, 1, 1, 1), "VALID")
    denom = jnp.power(1.0 + scale * acc, power)
    return (x.astype(jnp.float32) / denom).astype(x.dtype)


def sum_to_one_norm(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Normalize rows to sum 1 (reference: SumToOneNormLayer.cpp)."""
    return x / (jnp.sum(x, axis=-1, keepdims=True) + eps)


def row_l2_norm(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Row-wise L2 normalization (reference: RowL2NormLayer.cpp)."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)
