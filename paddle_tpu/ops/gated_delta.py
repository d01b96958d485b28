"""The gated delta-rule mixing layer (Gated DeltaNet linear attention).

Per value head a state ``S`` in ``R^{d_k x d_v}`` starts from zero with
each sequence and, token by token::

    S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T
    o_t = S^T q_t

That recurrence is the definition (``benchmarks/references/qwen3_next.py``
and the tests run it).  Here it is computed in CHUNKED form: inside a chunk
of ``CHUNK`` tokens the updates ``u_i = beta_i r_i`` solve the
unit-lower-triangular system ``(I + A) u = beta v - (beta a k) S_0`` with
``A_ij = beta_i D_ij (k_i . k_j)`` for ``j < i``, ``D_ij = exp(G_i - G_j)``,
``G`` the running sum of ``g`` inside the chunk and ``a_i = exp(G_i)``;
``(I + A)^{-1}`` is formed once a chunk by block forward substitution and
applied to ``beta v`` and ``beta a k``, and the chunks are chained through
``S``.  All of that is two Pallas kernels, forward and backward
(``ops/gdn_kernels.py``): a chunk's ``CHUNK x CHUNK`` tiles and the state
live in VMEM; ``gated_delta_rule`` lays the buffer out for them.

The tokens lie in one flat buffer with ``segment_ids``; a sequence may
start anywhere in a chunk.  Three masks carry that: ``D`` is zero across
segments, a token reads the incoming state only if its segment began
before the chunk, and only the tokens of the chunk's last segment write
the outgoing one.

Float32 throughout: ``g``, ``G``, ``D``, the inverse and the carried ``S``.
Under ``FLAGS.use_bf16`` the products take bfloat16 operands and accumulate
in float32, except those that form the inverse, which stay float32 at the
highest precision.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.ops import gdn_conv_kernels, gdn_kernels
from paddle_tpu.ops import math as pmath
from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.topology import keep

CHUNK = 64


def causal_conv(x: jax.Array, w: jax.Array, segment_ids: jax.Array,
                carry: Optional[jax.Array] = None,
                bias: Optional[jax.Array] = None):
    """Causal depthwise convolution inside each segment of a flat buffer:
    ``y_t = sum_j w[:, j] x_{t - (K - 1) + j} (+ bias)``, a row before its
    own sequence's start counting as zero.  x: [T, C]; w: [C, K] (the last
    tap meets the current token); bias: [C].  Float32.

    ``carry`` ``[segments, K - 1, C]`` hands in the rows BEFORE each
    segment's first (a sequence that continues from an earlier call): a
    row's id is then its segment's index there, a row with a negative id
    belongs to none, and ``(y, carry)`` comes back, the new carry holding
    the last ``K - 1`` rows of every segment (the old ones moved up where
    a segment brought fewer; a segment with no row keeps its own)."""
    t, taps = x.shape[0], w.shape[1]
    w = w.astype(jnp.float32)
    # every tap reads a window of ONE padded copy, so the taps fuse into a
    # single pass over it; rows of one sequence are contiguous, so the row
    # s back belongs to this sequence exactly if it carries this row's id
    xp = jnp.pad(x.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
    y = xp[taps - 1:] * w[:, taps - 1]
    if carry is None:
        sp = jnp.pad(segment_ids, (taps - 1, 0), constant_values=-1)
        for s in range(1, taps):
            lo = taps - 1 - s
            same = (sp[lo:lo + t] == segment_ids)[:, None]
            y = y + jnp.where(same, xp[lo:lo + t], 0.0) * w[:, lo]
        return y if bias is None else y + bias.astype(jnp.float32)
    k1, n_seg = taps - 1, carry.shape[0]
    carry = carry.astype(jnp.float32)
    idx = jnp.arange(t, dtype=jnp.int32)
    seg = segment_ids.astype(jnp.int32)
    begins = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    # a row's place in its segment, and its segment's carry (any for a row
    # of none)
    place = idx - jax.lax.cummax(jnp.where(begins, idx, 0))
    own = jnp.clip(seg, 0, n_seg - 1)
    for s in range(1, taps):
        lo = k1 - s
        # the row s back: of this buffer, or (before the segment's first)
        # entry ``place - s`` from the carry's end
        before = carry[own, jnp.clip(k1 - s + place, 0, k1 - 1)]
        y = y + jnp.where((place >= s)[:, None], xp[lo:lo + t],
                          before) * w[:, lo]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    # the last K - 1 rows of [carry | the segment's rows], a segment
    where = jnp.where(seg >= 0, seg, n_seg)             # (none: dropped)
    count = jnp.zeros((n_seg,), jnp.int32).at[where].add(1, mode="drop")
    first = jnp.full((n_seg,), t, jnp.int32).at[where].min(idx, mode="drop")
    at = count[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    old = jnp.take_along_axis(carry, jnp.clip(at, 0, k1 - 1)[:, :, None],
                              axis=1)
    new = x.astype(jnp.float32)[jnp.clip(first[:, None] + at - k1, 0, t - 1)]
    return y, jnp.where((at < k1)[:, :, None], old, new)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, segment_ids: jax.Array) -> jax.Array:
    """The recurrence above in chunked form.  q, k: [T, Hk, dk] (as they
    enter the recurrence: normalised, q scaled); v: [T, Hv, dv]; g (log
    decay, <= 0) and beta: [T, Hv]; ``Hv`` a multiple of ``Hk``, key head
    ``h // (Hv / Hk)`` serving value head ``h``.  Returns o [T, Hv, dv]
    float32.  ``T`` is padded to a multiple of ``CHUNK`` with rows of a
    segment of their own."""
    t, hk, _ = k.shape
    hv, dv = v.shape[1], v.shape[2]
    rep = hv // hk
    ct = pmath.compute_dtype(v)
    c = CHUNK
    pad = -t % c
    if pad:
        rows = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        q, k, v, g, beta = (rows(a) for a in (q, k, v, g, beta))
        segment_ids = jnp.pad(segment_ids, (0, pad),
                              constant_values=jnp.iinfo(jnp.int32).max)
    n = (t + pad) // c

    def chunks(a):          # [T, Hv] -> [n, Hk, rep, c]
        return jnp.swapaxes(a.astype(jnp.float32).reshape(n, c, hv), 1, 2
                            ).reshape(n, hk, rep, c)

    seg = segment_ids.reshape(n, c)
    before = jnp.concatenate([jnp.full((1,), -1, seg.dtype), seg[:-1, -1]])
    reads = seg == before[:, None]                      # [n, c]
    # a row's first row of the same segment: equal in a chunk where the
    # ids are, and small enough to be exact in float32
    first = jnp.argmax(seg[:, :, None] == seg[:, None, :], axis=-1)
    marks = jnp.tile(jnp.stack([first, reads, seg == seg[:, -1:]], axis=1
                               ).astype(jnp.float32), (1, 1, rep))
    cum = jnp.cumsum(chunks(g), axis=-1)                # G, [n, Hk, rep, c]
    # a key head's value heads side by side: [n, Hk, 2, rep c]
    scalars = jnp.stack([cum, chunks(beta)], axis=2).reshape(
        n, hk, 2, rep * c)
    q2, k2, v2 = (a.reshape(n * c, -1) for a in (q, k, v))

    o = gdn_kernels.delta_rule_chunks(q2, k2, v2, scalars, marks, ct)
    return o.reshape(n * c, hv, dv)[:t]


def conv_is_fused(num_k_heads: int, num_v_heads: int, head_k_dim: int,
                  head_v_dim: int) -> bool:
    """Whether ``gated_delta_net`` at these widths takes q, k, v from the
    fused kernels: told from the shapes alone."""
    return gdn_conv_kernels.lane_block(gdn_conv_kernels.Dims(
        num_k_heads, num_v_heads, head_k_dim, head_v_dim)) is not None


def qkv_conv_xla(qkvz: jax.Array, w: jax.Array, segment_ids: jax.Array,
                 dims: gdn_conv_kernels.Dims):
    """``gdn_conv_kernels.qkv_conv`` composed from ``causal_conv``: what
    the layer runs at widths the kernels do not take, and what the tests
    hold the kernels to."""
    t, nq = qkvz.shape[0], dims.nq
    qkv = jax.nn.silu(causal_conv(qkvz[:, :dims.channels], w, segment_ids))
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
    q = unit(qkv[:, :nq].reshape(t, dims.hk, dims.dk)) \
        * float(dims.dk) ** -0.5
    k = unit(qkv[:, nq:2 * nq].reshape(t, dims.hk, dims.dk))
    return q.reshape(t, nq), k.reshape(t, nq), qkv[:, 2 * nq:]


def gated_delta_net(x: jax.Array, segment_ids: jax.Array,
                    p: Dict[str, jax.Array], *, num_k_heads: int,
                    num_v_heads: int, head_k_dim: int, head_v_dim: int,
                    eps: float = 1e-6) -> jax.Array:
    """The whole mixing layer over one flat buffer.  x: [T, hidden];
    segment_ids: [T].  ``p``: ``w_qkvz`` [hidden, 2 Hk dk + 2 Hv dv] (columns
    ``[q | k | v | z]``), ``w_ba`` [hidden, 2 Hv] (``[b | a]``), ``conv``
    [2 Hk dk + Hv dv, K], ``a_log``, ``dt_bias`` [Hv], ``norm`` [dv], ``wo``
    [Hv dv, hidden].  No biases.  Returns [T, hidden] float32.

    ``[q|k|v] <- silu(conv(q|k|v))``; q and k are L2-normalised per head and
    q scaled by ``dk ** -0.5``; ``beta = sigmoid(b)``, ``g = -exp(a_log)
    softplus(a + dt_bias)``; after the recurrence ``y = RMSNorm(o) * norm
    * silu(z)`` per head, times ``wo``.

    From ``qkvz`` to q, k, v is one Pallas kernel each way
    (``ops/gdn_conv_kernels.py``) where the heads fill whole 128-lane tiles
    (``conv_is_fused``); at other widths the same steps stay with XLA."""
    t = x.shape[0]
    hk, hv, dk, dv = num_k_heads, num_v_heads, head_k_dim, head_v_dim
    nv = hv * dv
    with jax.named_scope("gdn"):
        with jax.named_scope("gdn.proj"):
            # kept across a recomputed segment: the prologue's backward
            # kernel and ``gdn.out`` read qkvz, the gates ba
            qkvz, ba = keep("gdn_proj", pmath.matmul(x, p["w_qkvz"]),
                            pmath.matmul(x, p["w_ba"]))
        with jax.named_scope("gdn.conv"):
            dims = gdn_conv_kernels.Dims(hk, hv, dk, dv)
            prologue = gdn_conv_kernels.qkv_conv \
                if conv_is_fused(*dims) else qkv_conv_xla
            q, k, v = (a.reshape(t, -1, d) for a, d in zip(
                prologue(qkvz, p["conv"], segment_ids, dims), (dk, dk, dv)))
            beta = jax.nn.sigmoid(ba[:, :hv])
            g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
                ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
        with jax.named_scope("gdn.scan"):
            o = gated_delta_rule(q, k, v, g, beta, segment_ids)
        with jax.named_scope("gdn.out"):
            z = qkvz[:, dims.channels:].reshape(t, hv, dv)
            y = rms_norm(o, p["norm"].astype(jnp.float32), eps) \
                * jax.nn.silu(z)
            return pmath.matmul(y.reshape(t, nv), p["wo"])
