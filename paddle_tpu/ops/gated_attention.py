"""Gated grouped-query attention: fewer key/value heads than query heads,
a weighted RMSNorm on every head's query and key, rotary positions on the
first ``rotary_dim`` of a head's dims, and an output gate that the query
projection makes beside the query.

The training kernels (``ops/attention.py flash_attention``) take K and V
with as many heads as Q, so the key/value heads are broadcast to the query
heads before the call (query head ``h`` reads KV head ``h // (H / H_kv)``)
and their gradients summed back by the broadcast's own transpose.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention as pattn
from paddle_tpu.ops import math as pmath
from paddle_tpu.ops.kernel_util import per_device
from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.ops.rotary import rotary


def gated_attention(x: jax.Array, positions: jax.Array,
                    segment_ids: jax.Array, p: Dict[str, jax.Array], *,
                    num_heads: int, num_kv_heads: int, head_dim: int,
                    rotary_dim: int, eps: float = 1e-6,
                    theta: float = 10000.0, mesh=None) -> jax.Array:
    """Causal self-attention inside each segment of one flat buffer.

    x: [T, hidden]; positions, segment_ids: [T].  ``p``: ``wq`` [hidden,
    H 2 D] (per head ``[query | gate]``), ``wk``, ``wv`` [hidden, H_kv D],
    ``q_norm``, ``k_norm`` [D], ``wo`` [H D, hidden].  No biases.  Scores
    are scaled by ``D ** -0.5``; the result is ``(attention *
    sigmoid(gate)) wo``, [T, hidden] float32."""
    t = x.shape[0]
    h, kv, d = num_heads, num_kv_heads, head_dim
    assert h % kv == 0 and rotary_dim <= d, (h, kv, rotary_dim, d)
    with jax.named_scope("gattn"):
        ct = pmath.compute_dtype(x)
        qg = pmath.matmul(x, p["wq"]).reshape(t, h, 2 * d)
        q = rms_norm(qg[..., :d], p["q_norm"], eps)
        k = rms_norm(pmath.matmul(x, p["wk"]).reshape(t, kv, d),
                     p["k_norm"], eps)
        v = pmath.matmul(x, p["wv"]).reshape(t, kv, d)

        def turn(a):
            return jnp.concatenate(
                [rotary(a[..., :rotary_dim], positions, theta),
                 a[..., rotary_dim:]], axis=-1)

        wide = lambda a: jnp.repeat(a, h // kv, axis=1)  # noqa: E731
        out = per_device(
            lambda q_, k_, v_, s_: pattn.flash_attention(
                q_, k_, v_, segment_ids=s_, causal=True,
                sm_scale=float(d) ** -0.5),
            mesh)(turn(q)[None].astype(ct), wide(turn(k))[None].astype(ct),
                  wide(v)[None].astype(ct), segment_ids[None, :])
        out = out[0].astype(jnp.float32) * jax.nn.sigmoid(qg[..., d:])
        return pmath.matmul(out.reshape(t, h * d), p["wo"])
