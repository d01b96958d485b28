"""Multi-head latent attention (MLA), the expanded form used in training.

Queries and keys/values go through low-rank bottlenecks with a weighted
RMSNorm inside; each head's query and key are a position-free part
(``qk_nope_dim``) beside a rotary part (``qk_rope_dim``), and the rotary
part of the key is ONE vector shared by all heads.  Values have their own
head width.  The absorbed form (scores against the cached latent) is the
serving path's and is not here; this function is the arithmetic both
share up to that point.
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


def mla_attention(x: jax.Array, positions: jax.Array, segment_ids: jax.Array,
                  p: Dict[str, jax.Array], *, num_heads: int,
                  qk_nope_dim: int, qk_rope_dim: int, v_dim: int,
                  eps: float = 1e-5, theta: float = 10000.0, mesh=None
                  ) -> jax.Array:
    """Causal latent self-attention inside each segment of one flat buffer.

    x: [T, hidden]; positions, segment_ids: [T].  ``p``: ``wq_a`` [hidden,
    q_rank], ``q_norm`` [q_rank], ``wq_b`` [q_rank, H (nope + rope)],
    ``wkv_a`` [hidden, kv_rank + rope], ``kv_norm`` [kv_rank], ``wkv_b``
    [kv_rank, H (nope + v)], ``wo`` [H v, hidden].  No biases.  Scores are
    scaled by ``(nope + rope) ** -0.5``.  Returns [T, hidden] float32.

    The flash kernel takes one head width, so values narrower than
    ``nope + rope`` are zero-padded to it (and the output cut back); the
    published widths (192 + 64 against 256) need no padding."""
    t = x.shape[0]
    h, dq = num_heads, qk_nope_dim + qk_rope_dim
    kv_rank = p["kv_norm"].shape[0]
    assert v_dim <= dq, (v_dim, dq)
    with jax.named_scope("mla"):
        ct = pmath.compute_dtype(x)
        cq = rms_norm(pmath.matmul(x, p["wq_a"]), p["q_norm"], eps)
        q = pmath.matmul(cq, p["wq_b"]).reshape(t, h, dq)
        ckv = pmath.matmul(x, p["wkv_a"])
        c = rms_norm(ckv[:, :kv_rank], p["kv_norm"], eps)
        kv = pmath.matmul(c, p["wkv_b"]).reshape(t, h, qk_nope_dim + v_dim)
        q_rope = rotary(q[..., qk_nope_dim:], positions, theta)
        k_rope = rotary(ckv[:, None, kv_rank:], positions, theta)
        qf = jnp.concatenate([q[..., :qk_nope_dim], q_rope], axis=-1)
        kf = jnp.concatenate(
            [kv[..., :qk_nope_dim],
             jnp.broadcast_to(k_rope, (t, h, qk_rope_dim))], axis=-1)
        v = kv[..., qk_nope_dim:]
        if v_dim < dq:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, dq - v_dim)))
        seg = segment_ids[None, :]
        out = per_device(
            lambda q_, k_, v_, s_: pattn.flash_attention(
                q_, k_, v_, segment_ids=s_, causal=True,
                sm_scale=float(dq) ** -0.5),
            mesh)(qf[None].astype(ct), kf[None].astype(ct),
                  v[None].astype(ct), seg)
        out = out[0, :, :, :v_dim].reshape(t, h * v_dim)
        return pmath.matmul(out, p["wo"])
