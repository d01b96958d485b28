"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference (2017) has no long-context parallelism — its long-sequence
story is the ragged Argument/LoD representation plus RecurrentGradientMachine
frame batching (SURVEY.md §2.3 'Sequence parallelism' row).  This module is
the TPU-native extension that carries that capability to modern scale:

  - ``ring_attention``: q/k/v sharded along the sequence dim over a mesh
    axis; kv chunks rotate around the ring via ``lax.ppermute`` (ICI
    neighbour exchange), each step merged with online-softmax (m, l, acc)
    accumulation.  Communication overlaps compute the way the reference's
    MultiGradientMachine pipelined its ring gradient copies
    (MultiGradientMachine.h:60-90) — here XLA does the overlap.
  - ``ulysses_attention``: all_to_all head<->sequence reshard (the sparse
    all-to-all machinery of SURVEY §2.3 applied to attention): each device
    gets the full sequence for a subset of heads, runs local (flash)
    attention, and resharding back.

Both are plain shard_map programs: autodiff flows through ppermute /
all_to_all transposes, so training works without hand-written backward.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.ops.attention import DEFAULT_MASK_VALUE, flash_attention


def _chunk_attn(q, k, v, q_seg, k_seg, q_off, k_off, causal, sm_scale):
    """One q-chunk x kv-chunk blockwise attention; returns (acc, m, l).

    q: (B, Sq, H, D); k/v: (B, Sk, H, D); offsets are global token offsets
    of the chunks (for causal masking across the ring).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    mask = (q_seg[:, None, :, None] == k_seg[:, None, None, :])
    if causal:
        q_ids = q_off + jnp.arange(q.shape[1])
        k_ids = k_off + jnp.arange(k.shape[1])
        mask = mask & (q_ids[None, None, :, None] >= k_ids[None, None, None, :])
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1)                        # (B,H,Sq)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                        # (B,H,Sq)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return acc, m, l


def _merge(acc, m, l, acc2, m2, l2):
    m_new = jnp.maximum(m, m2)
    a1 = jnp.exp(m - m_new)
    a2 = jnp.exp(m2 - m_new)
    l_new = l * a1 + l2 * a2
    acc_new = (acc * a1.transpose(0, 2, 1)[..., None]
               + acc2 * a2.transpose(0, 2, 1)[..., None])
    return acc_new, m_new, l_new


def ring_attention(q, k, v, mesh, axis: str = "seq", segment_ids=None,
                   causal: bool = False, sm_scale: Optional[float] = None):
    """Ring self-attention over sequence-sharded q/k/v.

    Args:
      q, k, v: (B, S, H, D) arrays logically sharded (B, S/axis, H, D) —
        pass the global arrays; shard_map partitions them.
      segment_ids: (B, S) int32 packed-segment ids (None => one segment).
    Returns (B, S, H, D) with the same sequence sharding as q.
    """
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    n = mesh.shape[axis]
    batch, seq, heads, head_dim = q.shape
    assert seq % n == 0, f"seq {seq} must divide over axis {axis}={n}"
    local = seq // n
    if segment_ids is None:
        segment_ids = jnp.zeros((batch, seq), jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)

    def body(q, k, v, seg):
        # all args are the local shards: (B, local, H, D) / (B, local)
        idx = jax.lax.axis_index(axis)
        q_off = idx * local

        def step(t, carry):
            acc, m, l, kc, vc, segc = carry
            src = jax.lax.rem(idx - t + n, n)       # origin device of chunk
            k_off = src * local
            acc2, m2, l2 = _chunk_attn(q, kc, vc, seg, segc, q_off, k_off,
                                       causal, sm_scale)
            acc, m, l = _merge(acc, m, l, acc2, m2, l2)
            perm = [(i, (i + 1) % n) for i in range(n)]
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            segc = jax.lax.ppermute(segc, axis, perm)
            return acc, m, l, kc, vc, segc

        # shard_map tracks varying-manual-axes: the carry becomes
        # axis-varying after one step, so it must start that way
        acc0, m0, l0 = jax.lax.pcast(
            (jnp.zeros((batch, local, heads, head_dim), jnp.float32),
             jnp.full((batch, heads, local), -jnp.inf, jnp.float32),
             jnp.zeros((batch, heads, local), jnp.float32)),
            (axis,), to="varying")
        acc, m, l, _, _, _ = jax.lax.fori_loop(
            0, n, step, (acc0, m0, l0, k, v, seg))
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l.transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    spec = P(None, axis, None, None)
    seg_spec = P(None, axis)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec, spec, spec, seg_spec),
                   out_specs=spec)
    return fn(q, k, v, segment_ids)


def ulysses_attention(q, k, v, mesh, axis: str = "seq", segment_ids=None,
                      causal: bool = False, sm_scale: Optional[float] = None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """DeepSpeed-Ulysses-style sequence parallelism.

    q/k/v sequence-sharded over ``axis``; all_to_all resharding gives each
    device ALL tokens for heads/axis_size heads; local flash attention; then
    all_to_all back to sequence sharding.  Heads must divide by axis size.
    """
    n = mesh.shape[axis]
    batch, seq, heads, head_dim = q.shape
    assert heads % n == 0, f"heads {heads} must divide over {axis}={n}"
    assert seq % n == 0
    if segment_ids is None:
        segment_ids = jnp.zeros((batch, seq), jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)

    def body(q, k, v, seg):
        # local: (B, S/n, H, D) -> (B, S, H/n, D)
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
        seg_full = jax.lax.all_gather(seg, axis, axis=1, tiled=True)
        out = flash_attention(qh, kh, vh, segment_ids=seg_full,
                              causal=causal, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    # replication check off: pallas_call inside shard_map doesn't
    # annotate vma
    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec, spec, spec, P(None, axis)),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v, segment_ids)
