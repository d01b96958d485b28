"""Sequence/segment kernels — the ragged-sequence op family.

Reference: paddle/gserver/layers/SequencePoolLayer.cpp (max/avg/sum over each
sequence), SequenceLastInstanceLayer.cpp (seqlastins/first), ExpandLayer.cpp,
SequenceConcatLayer.cpp, SequenceReshapeLayer.cpp, SeqSliceLayer.cpp,
SubNestedSequenceLayer.cpp, KmaxSeqScore (gserver/layers), MaxIdLayer.cpp, and the
sequence_softmax activation (ActivationFunction.cpp).

TPU-native: all ops work on the flat segment-ids form (paddle_tpu.sequence.
SequenceBatch) using jax segment reductions — no per-sequence loops, fully
static shapes, pad slots masked out.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.sequence import SequenceBatch, position_in_sequence


def _seg(sb: SequenceBatch) -> jax.Array:
    """Segment ids with pads mapped to an extra trash segment (= num_seqs)."""
    return jnp.where(sb.valid_mask, sb.segment_ids, sb.num_seqs)


def seq_pool_sum(sb: SequenceBatch) -> jax.Array:
    out = jax.ops.segment_sum(sb.data, _seg(sb), num_segments=sb.num_seqs + 1)
    return out[: sb.num_seqs]


def seq_pool_avg(sb: SequenceBatch) -> jax.Array:
    s = seq_pool_sum(sb)
    denom = jnp.maximum(sb.lengths, 1).astype(s.dtype)
    return s / denom.reshape((-1,) + (1,) * (s.ndim - 1))


def seq_pool_sqrtn(sb: SequenceBatch) -> jax.Array:
    s = seq_pool_sum(sb)
    denom = jnp.sqrt(jnp.maximum(sb.lengths, 1).astype(s.dtype))
    return s / denom.reshape((-1,) + (1,) * (s.ndim - 1))


def seq_pool_max(sb: SequenceBatch) -> jax.Array:
    neg = jnp.full_like(sb.data, -jnp.inf if jnp.issubdtype(sb.data.dtype, jnp.floating)
                        else jnp.iinfo(sb.data.dtype).min)
    masked = jnp.where(sb.valid_mask.reshape((-1,) + (1,) * (sb.data.ndim - 1)),
                       sb.data, neg)
    out = jax.ops.segment_max(masked, _seg(sb), num_segments=sb.num_seqs + 1)
    return out[: sb.num_seqs]


def seq_first(sb: SequenceBatch) -> jax.Array:
    """First token of each sequence (reference: SequenceLastInstanceLayer with
    select_first)."""
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sb.lengths)[:-1].astype(jnp.int32)])
    return sb.data[starts]


def seq_last(sb: SequenceBatch) -> jax.Array:
    """Last token of each sequence (reference: seqlastins)."""
    ends = jnp.cumsum(sb.lengths).astype(jnp.int32) - 1
    ends = jnp.maximum(ends, 0)
    return sb.data[ends]


def sequence_softmax(sb: SequenceBatch) -> SequenceBatch:
    """Softmax over each sequence's scalar scores (reference:
    sequence_softmax activation). data: [capacity] or [capacity, 1]."""
    x = sb.data
    squeeze = x.ndim > 1
    if squeeze:
        x = x[..., 0]
    seg = _seg(sb)
    n = sb.num_seqs + 1
    x = jnp.where(sb.valid_mask, x, -jnp.inf)
    mx = jax.ops.segment_max(x, seg, num_segments=n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.where(sb.valid_mask, jnp.exp(x - mx[seg]), 0.0)
    z = jax.ops.segment_sum(ex, seg, num_segments=n)
    out = ex / jnp.maximum(z[seg], 1e-30)
    if squeeze:
        out = out[..., None]
    return sb.with_data(out.astype(sb.data.dtype))


def seq_expand(sb_short, sb_long: SequenceBatch) -> SequenceBatch:
    """Expand per-sequence (or per-token) values of `sb_short` to the token
    layout of `sb_long` (reference: ExpandLayer.cpp).

    sb_short may be a dense [num_seqs, ...] array (one row per sequence).
    """
    if isinstance(sb_short, SequenceBatch):
        values = seq_first(sb_short)  # one representative per sequence
    else:
        values = sb_short
    seg = jnp.clip(sb_long.segment_ids, 0, values.shape[0] - 1)
    data = values[seg]
    mask = sb_long.valid_mask.reshape((-1,) + (1,) * (data.ndim - 1))
    return sb_long.with_data(jnp.where(mask, data, 0))


def seq_concat(a: SequenceBatch, b: SequenceBatch) -> SequenceBatch:
    """Concatenate sequence i of `a` with sequence i of `b` along time
    (reference: SequenceConcatLayer.cpp)."""
    pa, _ = a.to_padded()
    pb, mb = b.to_padded()
    B = a.num_seqs
    Tb = pb.shape[1]
    lengths = a.lengths + b.lengths
    # Place b's tokens after a's true length by scattering into [B, Ta+Tb, ...].
    out = jnp.concatenate([pa, jnp.zeros_like(pb)], axis=1)
    t_idx = jnp.arange(Tb, dtype=jnp.int32)[None, :] + a.lengths[:, None]
    t_idx = jnp.where(mb, t_idx, out.shape[1])  # invalid b-slots scatter off-range (dropped)
    b_rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, Tb))
    out = out.at[b_rows, t_idx].set(pb, mode="drop")
    return SequenceBatch.from_padded(out, lengths, capacity=a.capacity + b.capacity)


def seq_reshape(sb: SequenceBatch, new_dim: int) -> SequenceBatch:
    """Reshape each sequence's [len, d] to [len*d/new_dim, new_dim]
    (reference: SequenceReshapeLayer.cpp). Requires contiguous tokens."""
    d = sb.data.shape[-1]
    cap = sb.capacity * d // new_dim
    data = sb.data.reshape(cap, new_dim)
    new_lengths = (sb.lengths * d) // new_dim
    from paddle_tpu.sequence import lengths_to_segment_ids
    seg = lengths_to_segment_ids(new_lengths, cap)
    new_max = None if sb.max_len is None else max(1, sb.max_len * d // new_dim)
    return SequenceBatch(data=data, segment_ids=seg, lengths=new_lengths,
                         max_len=new_max)

def seq_slice(sb: SequenceBatch, starts: jax.Array, ends: jax.Array) -> SequenceBatch:
    """Keep tokens with start<=pos<end per sequence (reference: SeqSliceLayer).

    Returns the same capacity with a new mask/lengths (tokens compacted left
    per-sequence is not required by downstream segment ops)."""
    pos = position_in_sequence(sb.segment_ids)
    seg = jnp.clip(sb.segment_ids, 0, sb.num_seqs - 1)
    keep = sb.valid_mask & (pos >= starts[seg]) & (pos < ends[seg])
    new_lengths = jnp.clip(jnp.minimum(ends, sb.lengths) - starts, 0, None)
    seg_ids = jnp.where(keep, sb.segment_ids, sb.num_seqs)
    mask = keep.reshape((-1,) + (1,) * (sb.data.ndim - 1))
    return SequenceBatch(data=jnp.where(mask, sb.data, 0), segment_ids=seg_ids,
                         lengths=new_lengths.astype(jnp.int32),
                         max_len=sb.max_len)


def kmax_seq_score(sb: SequenceBatch, k: int) -> jax.Array:
    """Indices (positions within each sequence) of the top-k scores
    (reference: KmaxSeqScore (gserver/layers)). data: [capacity] or [capacity,1].
    Returns [num_seqs, k] int32 positions (padded with -1)."""
    scores, mask = sb.with_data(
        sb.data[..., 0] if sb.data.ndim > 1 else sb.data).to_padded()
    scores = jnp.where(mask, scores, -jnp.inf)
    _, idx = jax.lax.top_k(scores, k)
    valid = jnp.take_along_axis(mask, idx, axis=1)
    return jnp.where(valid, idx, -1).astype(jnp.int32)


def max_id(x: jax.Array) -> jax.Array:
    """Argmax along the last dim (reference: MaxIdLayer.cpp)."""
    return jnp.argmax(x, axis=-1).astype(jnp.int32)


def sub_nested_seq(sb: SequenceBatch, selected: jax.Array) -> SequenceBatch:
    """Select inner sequences from a nested sequence batch (reference:
    SubNestedSequenceLayer.cpp). `selected`: [num_seqs, k] inner indices
    (-1 = none). Tokens of unselected inner seqs are masked out."""
    if sb.sub_segment_ids is None:
        raise ValueError("sub_nested_seq requires nested SequenceBatch")
    seg = jnp.clip(sb.segment_ids, 0, sb.num_seqs - 1)
    sel = selected[seg]  # [capacity, k]
    keep = jnp.any(sel == sb.sub_segment_ids[:, None], axis=-1) & sb.valid_mask
    seg_ids = jnp.where(keep, sb.segment_ids, sb.num_seqs)
    n = sb.num_seqs + 1
    new_lengths = jax.ops.segment_sum(keep.astype(jnp.int32),
                                      jnp.where(keep, seg, sb.num_seqs),
                                      num_segments=n)[: sb.num_seqs]
    mask = keep.reshape((-1,) + (1,) * (sb.data.ndim - 1))
    return SequenceBatch(data=jnp.where(mask, sb.data, 0), segment_ids=seg_ids,
                         lengths=new_lengths, max_len=sb.max_len)
