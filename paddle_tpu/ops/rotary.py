"""Rotary position embedding (RoPE), the half-split pairing.

Dimension ``i`` of the first half is rotated with dimension ``i`` of the
second half by the angle ``position * theta ** (-2 i / d)``: the pairing
of the published transformer libraries (``rotate_half``), which for a
model whose checkpoints pair neighbours (2i, 2i + 1) is reached by a fixed
permutation of the projection's columns.  No table is stored: the angles
are computed from the positions a batch feeds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [T, ..., d] with d even; positions: [T] (position of each row
    inside its own sequence).  Computed in f32, returned in x.dtype."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)
