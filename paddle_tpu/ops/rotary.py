"""Rotary position embedding (RoPE), the half-split pairing.

Dimension ``i`` of the first half is rotated with dimension ``i`` of the
second half by the angle ``position * theta ** (-2 i / d)``: the pairing
of the published transformer libraries (``rotate_half``), which for a
model whose checkpoints pair neighbours (2i, 2i + 1) is reached by a fixed
permutation of the projection's columns.  No table is stored: the angles
are computed from the positions a batch feeds.

:func:`rotary_lanes` is the general form: the frequencies are given (so a
scaled set, :func:`yarn_inv_freq`, can be), only the first ``2 *
len(inv_freq)`` lanes of a head turn (partial rotary) and cos and sin are
multiplied by ``scale`` (YaRN's attention factor).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


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


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_positions: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0, truncate: bool = True
                  ) -> np.ndarray:
    """The ``dim // 2`` YaRN frequencies (arXiv 2309.00071) over ``dim``
    rotary lanes, float32: a lane pair that turns more than ``beta_fast``
    times inside the original context keeps ``theta ** (-2 i / dim)``
    (extrapolation), one that turns less than ``beta_slow`` times takes
    that over ``factor`` (interpolation), and a linear ramp over the
    pair's index blends the two between.  The formula of the published
    transformer libraries' ``yarn`` rope type."""

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max_positions /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001                      # (no singular ramp)
    pos = np.float32(theta) ** (np.arange(0, dim, 2, dtype=np.float32)
                                / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    # ramp 0: extrapolate (the plain frequency), 1: interpolate (/ factor)
    return ((1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1 - ramp)
            ).astype(np.float32)


def rotary_lanes(x: jax.Array, positions: jax.Array,
                 inv_freq: Sequence[float], scale: float = 1.0) -> jax.Array:
    """x: [T, ..., d]; positions: [T].  The first ``2 * len(inv_freq)``
    lanes of the last axis turn, lane ``i`` with lane ``i +
    len(inv_freq)`` by ``position * inv_freq[i]``, cos and sin times
    ``scale``; the lanes behind them pass through.  Computed in f32,
    returned in x.dtype."""
    inv = jnp.asarray(np.asarray(inv_freq, np.float32))
    half = inv.shape[0]
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1).astype(x.dtype)
