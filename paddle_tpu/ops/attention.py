"""Blockwise (flash) attention for TPU — pallas kernel + pure-JAX reference.

This is the TPU-native successor of the reference's attention machinery
(trainer_config_helpers/networks.py:1304 simple_attention, :1402
dot_product_attention) extended to the modern multi-head form the new
framework needs for long-context support.  Segment-id masking plays the role
of the reference's ragged-sequence representation
(Argument.sequenceStartPositions, paddle/parameter/Argument.h:84-90;
LoDTensor, paddle/framework/lod_tensor.h:57): sequences are packed
back-to-back in one buffer and attention never crosses a segment boundary,
so there is no padding waste.

Design notes (TPU-first):
  - the kernels visit LIVE blocks only.  A (q block, k block) pair is live
    when the two blocks' segment-id ranges meet and the causal mask leaves a
    pair in it (``block_live``).  ``block_schedule`` lists the live pairs in
    XLA, once for all heads and all three kernels, sorted by the block that
    stays resident; the kernels take the lists as scalar-prefetch operands
    (``pltpu.PrefetchScalarGridSpec``) on a grid (batch, heads, visits)
    whose last extent is the TRACED number of visits, and their index maps
    read the block indices from them.  A packed buffer of 4 x 2048 tokens
    takes 40 steps a head where the 16 x 16 square has 256; no step is spent
    finding out that a block is dead, and no dead block's tiles are fetched.
  - forward: q block resident, its live key blocks STREAMED past it — only
    one (block_q x D) and one (block_k x D) tile is resident in VMEM, with
    the online-softmax carry (m, l, acc) in VMEM scratch from the q block's
    first visit to its last.  VMEM use is O(block^2) at ANY sequence length.
    Pallas double-buffers the streamed tiles, so the K/V DMA of the next
    visit overlaps this visit's matmuls; matmuls hit the MXU with
    block_q x head_dim x block_k shapes and fp32 accumulation.
  - backward is TWO pallas kernels (dK/dV: k block resident, its live QUERY
    blocks streamed; dQ: the forward's walk), each recomputing P blockwise
    from (q, k, lse) — the S x S score matrix never exists in either
    direction.  There is no plain-JAX backward: ``jax.grad`` through
    ``mha_reference`` is the oracle the tests compare against.
  - a resident block with no live pair (cross-attention over disjoint ids)
    keeps one visit that computes nothing, so its output is written as zeros.
  - on CPU (tests / 8-device virtual mesh) the same kernels run in interpret
    mode.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_BLOCK_LADDER = (512, 256, 128)   # default tile edges, largest first


from paddle_tpu.ops.kernel_util import interpret_default as _interpret_default


# ---------------------------------------------------------------------------
# Reference implementation (test oracle; also used for tiny shapes)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, segment_ids=None, kv_segment_ids=None,
                  causal: bool = False, sm_scale: Optional[float] = None):
    """Plain-JAX multi-head attention.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D); segment_ids: (B, Sq) int32,
    kv_segment_ids: (B, Sk).  Returns (B, Sq, H, D).

    GQA: k/v may carry FEWER heads than q (H_kv dividing H) — each
    group of H // H_kv query heads then attends over one shared KV head
    (query head h reads KV head h // group).  The heads are replicated
    here, so this stays the oracle for the serving kernel's head-group
    packing.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    mask = None
    if segment_ids is not None:
        kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
        mask = (segment_ids[:, None, :, None] == kv_seg[:, None, None, :])
    if causal:
        cm = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))[None, None]
        mask = cm if mask is None else (mask & cm)
    if mask is not None:
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# The block schedule: which blocks the kernels visit, made in XLA
# ---------------------------------------------------------------------------

# One visit of a kernel: the resident block (the one whose carry lives in
# VMEM scratch), the streamed block, and what the step does with them.
_FIRST, _LAST, _COMPUTE = 1, 2, 4


class Visits(NamedTuple):
    """A kernel's walk over the live blocks of each batch row, in order.

    ``resident``, ``streamed``, ``flags`` are [B, extent] int32: visit ``t``
    of row ``b`` holds block ``resident[b, t]`` in VMEM scratch and streams
    block ``streamed[b, t]`` past it.  ``flags`` says whether the resident
    block starts there (``_FIRST``: the carry is initialised), ends there
    (``_LAST``: the output is written) and whether the pair is computed
    (``_COMPUTE``).  ``count`` [B] is the number of visits of each row;
    the slots behind it repeat the row's last visit with no flag set, so
    they move no tile and do nothing (only a row shorter than the longest
    of its batch has such steps: the grid's extent is ``count.max()``)."""
    resident: jax.Array
    streamed: jax.Array
    flags: jax.Array
    count: jax.Array


def _causal_bound(nq: int, nk: int, block_q: int, block_k: int,
                  causal: bool) -> np.ndarray:
    """[nq, nk] bool, static: the blocks the causal mask leaves a pair in
    (``j * block_k < (i + 1) * block_q``); all of them without ``causal``."""
    if not causal:
        return np.ones((nq, nk), bool)
    i = np.arange(nq)[:, None]
    j = np.arange(nk)[None, :]
    return j * block_k < (i + 1) * block_q


def block_live(q_seg, kv_seg, block_q: int, block_k: int, causal: bool):
    """[B, nq, nk] bool: which (q block, k block) pairs the kernels compute.

    Packed sequences give each block an id range; disjoint ranges mean no
    ``q_seg == k_seg`` pair exists, so the whole block is dead.  The test is
    conservative (overlapping ranges without an equal pair still compute),
    hence correct for ANY id assignment.  The forward and both backward
    kernels walk schedules made from this ONE array, so ``lse`` is never
    consumed by a pair the forward skipped."""
    batch, seq_q = q_seg.shape
    nq, nk = seq_q // block_q, kv_seg.shape[1] // block_k
    qb = q_seg.reshape(batch, nq, 1, block_q)
    kb = kv_seg.reshape(batch, 1, nk, block_k)
    meet = ((qb.max(-1) >= kb.min(-1)) & (qb.min(-1) <= kb.max(-1)))
    return meet & _causal_bound(nq, nk, block_q, block_k, causal)


def _visits(live, bound: np.ndarray) -> Visits:
    """The walk over ``live`` [B, R, S] (resident blocks by streamed blocks)
    sorted by resident block, then streamed block.  A resident block with no
    live pair keeps one visit that computes nothing, so that its output is
    still written (as zeros).  ``bound`` [R, S] is what ``live`` can be at
    most: it gives the static extent of the arrays."""
    batch, nr, ns = live.shape
    extent = int(np.maximum(bound.sum(1), 1).sum())
    lone = ~live.any(-1, keepdims=True) & (jnp.arange(ns) == 0)
    visit = (live | lone).reshape(batch, nr * ns)
    count = visit.sum(-1).astype(jnp.int32)
    # a stable sort of "not visited" lists the visits first, in row-major
    # order; the tail repeats the last of them
    t = jnp.arange(extent, dtype=jnp.int32)
    order = jnp.argsort(~visit, axis=-1, stable=True).astype(jnp.int32)
    order = jnp.take_along_axis(
        order, jnp.minimum(t, count[:, None] - 1), axis=-1)
    resident, streamed = order // ns, order % ns
    valid = t < count[:, None]
    edge = jnp.ones((batch, 1), bool)
    changes = resident[:, 1:] != resident[:, :-1]
    first = jnp.concatenate([edge, changes], axis=1)
    last = jnp.concatenate([changes, edge], axis=1) | (t == count[:, None] - 1)
    compute = jnp.take_along_axis(live.reshape(batch, nr * ns), order, axis=-1)
    flags = valid * (_FIRST * first + _LAST * last + _COMPUTE * compute)
    return Visits(resident, streamed, flags.astype(jnp.int32), count)


def block_schedule(q_seg, kv_seg, block_q: int, block_k: int, causal: bool):
    """The kernels' walks, made in XLA from the segment ids, once for all
    heads: ``by_q`` (sorted by q block, then k block) is the forward's and
    dQ's, ``by_k`` (by k block, then q block) is dKV's."""
    live = block_live(q_seg, kv_seg, block_q, block_k, causal)
    bound = _causal_bound(live.shape[1], live.shape[2], block_q, block_k,
                          causal)
    return _visits(live, bound), _visits(live.transpose(0, 2, 1), bound.T)


def _auto_block(seq: int) -> int:
    """The default tile edge: the largest of the ladder that divides the
    sequence.  Streaming keeps VMEM at O(block^2), so big tiles are free
    memory-wise and each grid step amortises its fixed cost over 16x more
    MXU work than a 128 tile."""
    for edge in _BLOCK_LADDER:
        if seq % edge == 0:
            return edge
    return 128  # small/ragged seqs: the caller clamps to seq


def _blocks(seq_q: int, seq_k: int, block_q, block_k):
    block_q = min(block_q or _auto_block(seq_q), seq_q)
    block_k = min(block_k or _auto_block(seq_k), seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"sequence lengths ({seq_q},{seq_k}) must divide by blocks "
        f"({block_q},{block_k}) — DataFeeder pads capacity to multiples")
    return block_q, block_k


def flash_block_counts(segment_ids, kv_segment_ids=None, *,
                       causal: bool = False):
    """(live, whole): the blocks one forward call of ``flash_attention``
    visits for these segment ids at its default tiles, and the blocks it
    would visit were each row one sequence (the causal triangle, or the
    square without ``causal``).  For a layer's counters: under ``jit`` only
    the live test and a sum of it are left of the schedule here."""
    q_seg = segment_ids.astype(jnp.int32)
    kv_seg = (q_seg if kv_segment_ids is None
              else kv_segment_ids.astype(jnp.int32))
    block_q, block_k = _blocks(q_seg.shape[1], kv_seg.shape[1], None, None)
    by_q, _ = block_schedule(q_seg, kv_seg, block_q, block_k, causal)
    return by_q.count.sum(), q_seg.shape[0] * by_q.resident.shape[1]


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

_LANES = 128  # lane width for the (block_q, _LANES) m/l scratch carries


def _pv_operands(probs, other, pv_f32: bool):
    """Operand dtypes for the P/dS-side matmuls (PV, dV, dK, dQ).

    Default: cast the f32 probs/dS down to the tiles' native dtype so the
    MXU runs its fast path. ``pv_f32`` (FLAGS.attn_pv_f32): upcast the
    other operand instead — no softmax-prob rounding, slower f32 MXU."""
    if pv_f32:
        return probs, other.astype(jnp.float32)
    return probs.astype(other.dtype), other


def _step(res_ref, str_ref, flag_ref, extent: int):
    """This grid step's visit, read from the scalar-prefetch refs: batch
    row, resident block, streamed block and the three flags."""
    b = pl.program_id(0)
    at = b * extent + pl.program_id(2)
    flags = flag_ref[at]
    return (b, res_ref[at], str_ref[at], (flags & _FIRST) != 0,
            (flags & _LAST) != 0, (flags & _COMPUTE) != 0)


def _flash_fwd_kernel(res_ref, str_ref, flag_ref, q_ref, k_ref, v_ref,
                      qseg_ref, kseg_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *, sm_scale: float, causal: bool, extent: int,
                      pv_f32: bool):
    # grid (B, H, visits): q block ``qi`` is resident, its live key blocks
    # ``j`` are streamed past it; the online-softmax carries (m, l, acc)
    # persist in VMEM scratch from the q block's first visit to its last.
    # q_ref: (1, 1, block_q, D); k_ref/v_ref: (1, 1, block_k, D).
    # qseg_ref: (B, block_q); kseg_ref: (B, block_k) — full batch dim
    # because TPU block shapes must tile (8, 128) or span the whole dim.
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    b, qi, j, first, last, live = _step(res_ref, str_ref, flag_ref, extent)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _compute():
        # MXU inputs stay in the tiles' native dtype (bf16 under the
        # global compute policy; f32 in f32 models/tests) with f32
        # accumulation — an .astype(f32) before the dot would force the
        # ~4x-slower f32 MXU path. sm_scale is applied to the f32 product
        # (same math as pre-scaling q, better bf16 precision).
        q = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        q_seg = qseg_ref[b, :].reshape(block_q, 1)
        k_seg = kseg_ref[b, :].reshape(1, block_k)
        mask = (q_seg == k_seg)
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = mask & (q_ids >= k_ids)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        # m/l ride as (block_q, _LANES) lane-replicated values; a lane-max
        # recovers the scalar column
        m_prev = jnp.max(m_scr[...], axis=1, keepdims=True)
        l_prev = jnp.max(l_scr[...], axis=1, keepdims=True)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # FLAGS.attn_pv_f32: keep the PV operands in f32 (no softmax-prob
        # rounding) for accuracy-sensitive runs; default rides the fast
        # native-dtype MXU path
        pb, vmm = _pv_operands(p, vb, pv_f32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pb, vmm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(last)
    def _finalize():
        m = jnp.max(m_scr[...], axis=1, keepdims=True)
        l = jnp.max(l_scr[...], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m + jnp.log(l)


def _dim_semantics(grid_ndim: int, interpret: bool):
    """All grid dims parallel but the last: only the streamed axis carries
    scratch state, so megacore may split any earlier dim across cores."""
    if interpret:
        return None  # interpret mode ignores TPU compiler params
    sem = ("parallel",) * (grid_ndim - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def _visit_call(kernel, visits: Visits, batch: int, heads: int, *, in_specs,
                out_specs, out_shape, scratch_shapes, interpret: bool,
                name: str, **static):
    """``pallas_call`` on the grid (batch, heads, visits): the schedule rides
    as scalar-prefetch operands, the index maps take the blocks from it, and
    the last extent is the traced number of visits (of the longest row)."""
    extent = visits.resident.shape[1]
    call = pl.pallas_call(
        functools.partial(kernel, extent=extent, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(batch, heads, visits.count.max()),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape, compiler_params=_dim_semantics(3, interpret),
        interpret=interpret,
        name=name,
    )
    return functools.partial(call, visits.resident.reshape(-1),
                             visits.streamed.reshape(-1),
                             visits.flags.reshape(-1))


def _tile_specs(extent: int, batch: int, block_r: int, block_s: int,
                head_dim: int):
    """BlockSpec makers of a (batch, heads, visits) grid: ``res(w)`` /
    ``stream(w)`` give a [1, 1, block, w] tile of a [B, H, S, w] operand at
    the visit's resident / streamed block, ``res_seg`` / ``stream_seg`` the
    matching [B, block] tile of the segment ids."""
    def at(b, t):
        return b * extent + t

    def res(width=head_dim):
        return pl.BlockSpec(
            (1, 1, block_r, width),
            lambda b, h, t, r, s, f: (b, h, r[at(b, t)], 0))

    def stream(width=head_dim):
        return pl.BlockSpec(
            (1, 1, block_s, width),
            lambda b, h, t, r, s, f: (b, h, s[at(b, t)], 0))

    res_seg = pl.BlockSpec((batch, block_r),
                           lambda b, h, t, r, s, f: (0, r[at(b, t)]))
    stream_seg = pl.BlockSpec((batch, block_s),
                              lambda b, h, t, r, s, f: (0, s[at(b, t)]))
    return res, stream, res_seg, stream_seg


def _flash_fwd(q, k, v, q_seg, kv_seg, by_q: Visits, causal, sm_scale,
               block_q, block_k, interpret, pv_f32=False):
    batch, seq_q, heads, head_dim = q.shape
    # (B, S, H, D) -> (B, H, S, D) for contiguous per-head blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    res, stream, res_seg, stream_seg = _tile_specs(
        by_q.resident.shape[1], batch, block_q, block_k, head_dim)
    out_t, lse = _visit_call(
        _flash_fwd_kernel, by_q, batch, heads,
        in_specs=[res(), stream(), stream(), res_seg, stream_seg],
        out_specs=[res(), res(1)],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_q, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret, name="flash_fwd",
        sm_scale=sm_scale, causal=causal, pv_f32=pv_f32,
    )(qt, kt, vt, q_seg, kv_seg)
    return out_t.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# Backward: pallas kernels (dK/dV then dQ), mirroring the forward's
# blocking. Reference for what they replace: the reference's hand-fused
# CUDA attention-adjacent kernels (paddle/cuda/src/*.cu) — here the win is
# recomputing P blockwise from (q, k, lse) so the S x S matrix never
# exists, with fp32 accumulation on the MXU.
# ---------------------------------------------------------------------------


def _flash_bwd_kv_kernel(res_ref, str_ref, flag_ref, q_ref, k_ref, v_ref,
                         qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                         causal: bool, extent: int, pv_f32: bool):
    # grid (B, H, visits): k block ``kj`` is resident, its live QUERY blocks
    # ``i`` are streamed past it; dk/dv accumulate in VMEM scratch.
    # k_ref/v_ref: (1, 1, block_k, D); q/do: (1, 1, block_q, D);
    # lse/delta: (1, 1, block_q, 1); qseg: (B, block_q); kseg: (B, block_k)
    block_k = k_ref.shape[2]
    block_q = q_ref.shape[2]
    b, kj, i, first, last, live = _step(res_ref, str_ref, flag_ref, extent)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(live)
    def _compute():
        # native-dtype MXU operands, f32 accumulation (see forward kernel)
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        qb = q_ref[0, 0, :, :]
        dob = do_ref[0, 0, :, :]
        lseb = lse_ref[0, 0, :, :]
        deltab = delta_ref[0, 0, :, :]
        q_seg = qseg_ref[b, :].reshape(block_q, 1)
        k_seg = kseg_ref[b, :].reshape(1, block_k)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        mask = q_seg == k_seg
        if causal:
            q_ids = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = mask & (q_ids >= k_ids)
        p = jnp.where(mask, jnp.exp(s - lseb), 0.0)
        pb, domm = _pv_operands(p, dob, pv_f32)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            pb, domm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltab) * sm_scale
        dsb, qmm = _pv_operands(ds, qb, pv_f32)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            dsb, qmm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(res_ref, str_ref, flag_ref, q_ref, k_ref, v_ref,
                         qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, sm_scale: float, causal: bool,
                         extent: int, pv_f32: bool):
    # grid (B, H, visits), the forward's walk: q block ``qi`` is resident,
    # its live KEY blocks ``j`` are streamed; dq accumulates in VMEM scratch.
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    b, qi, j, first, last, live = _step(res_ref, str_ref, flag_ref, extent)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(live)
    def _compute():
        # native-dtype MXU operands, f32 accumulation (see forward kernel)
        qb = q_ref[0, 0, :, :]
        dob = do_ref[0, 0, :, :]
        lseb = lse_ref[0, 0, :, :]
        deltab = delta_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        q_seg = qseg_ref[b, :].reshape(block_q, 1)
        k_seg = kseg_ref[b, :].reshape(1, block_k)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        mask = q_seg == k_seg
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = mask & (q_ids >= k_ids)
        p = jnp.where(mask, jnp.exp(s - lseb), 0.0)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltab) * sm_scale
        dsb, kmm = _pv_operands(ds, kb, pv_f32)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            dsb, kmm, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(res, do, *, causal, sm_scale, block_q, block_k,
                      interpret, pv_f32=False):
    q, k, v, q_seg, kv_seg, by_q, by_k, out, lse = res
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32) *
                    out.transpose(0, 2, 1, 3).astype(jnp.float32),
                    axis=-1, keepdims=True)               # (B, H, Sq, 1)
    lse_t = lse[..., None]                                # (B, H, Sq, 1)
    static = dict(sm_scale=sm_scale, causal=causal, pv_f32=pv_f32)

    # --- dK/dV: k block resident, its live q blocks streamed ---
    kblk, qblk, kseg_blk, qseg_blk = _tile_specs(
        by_k.resident.shape[1], batch, block_k, block_q, head_dim)
    dk_t, dv_t = _visit_call(
        _flash_bwd_kv_kernel, by_k, batch, heads,
        in_specs=[qblk(), kblk(), kblk(), qseg_blk, kseg_blk, qblk(),
                  qblk(1), qblk(1)],
        out_specs=[kblk(), kblk()],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_k, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_k, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        interpret=interpret, name="flash_bwd_dkv", **static,
    )(qt, kt, vt, q_seg, kv_seg, dot, lse_t, delta)

    # --- dQ: the forward's walk ---
    qblk, kblk, qseg_blk, kseg_blk = _tile_specs(
        by_q.resident.shape[1], batch, block_q, block_k, head_dim)
    dq_t = _visit_call(
        _flash_bwd_dq_kernel, by_q, batch, heads,
        in_specs=[qblk(), kblk(), kblk(), qseg_blk, kseg_blk, qblk(),
                  qblk(1), qblk(1)],
        out_specs=qblk(),
        out_shape=jax.ShapeDtypeStruct((batch, heads, seq_q, head_dim),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret, name="flash_bwd_dq", **static,
    )(qt, kt, vt, q_seg, kv_seg, dot, lse_t, delta)

    return (dq_t.transpose(0, 2, 1, 3), dk_t.transpose(0, 2, 1, 3),
            dv_t.transpose(0, 2, 1, 3), None, None, None, None)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _flash_attention(q, k, v, q_seg, kv_seg, by_q, by_k, causal, sm_scale,
                     block_q, block_k, interpret, pv_f32):
    out, _ = _flash_fwd(q, k, v, q_seg, kv_seg, by_q, causal, sm_scale,
                        block_q, block_k, interpret, pv_f32=pv_f32)
    return out


def _fwd_rule(q, k, v, q_seg, kv_seg, by_q, by_k, causal, sm_scale, block_q,
              block_k, interpret, pv_f32):
    out, lse = _flash_fwd(q, k, v, q_seg, kv_seg, by_q, causal, sm_scale,
                          block_q, block_k, interpret, pv_f32=pv_f32)
    return out, (q, k, v, q_seg, kv_seg, by_q, by_k, out, lse)


def _bwd_rule(causal, sm_scale, block_q, block_k, interpret, pv_f32, res, do):
    return _flash_bwd_pallas(res, do, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, pv_f32=pv_f32)


_flash_attention.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q, k, v, segment_ids=None, kv_segment_ids=None,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise multi-head attention (pallas forward, blockwise backward).

    Args:
      q: (B, Sq, H, D); k, v: (B, Sk, H, D).
      segment_ids: (B, Sq) int32 packed-sequence ids; tokens only attend
        within their own segment (use -1 for padding: give padding its own
        id).  None => full attention.
      kv_segment_ids: (B, Sk); defaults to segment_ids (self-attention).
      causal: lower-triangular masking (positions are absolute in the packed
        buffer — combine with segment ids for per-sequence causality).
      block_q, block_k: tile edges; by default the largest of the ladder
        that divides the sequence (call sites that chose their blocks —
        ring/ulysses shard-sized tiles, tests — keep them).
    """
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    from paddle_tpu.platform.flags import FLAGS

    batch, seq_q = q.shape[0], q.shape[1]
    seq_k = k.shape[1]
    # the kernels feed operands to the MXU in their native dtype (no f32
    # upcast), which requires uniform q/k/v dtypes — normalize mixed-dtype
    # calls (e.g. a bf16 query against an f32 KV cache) to q's dtype here
    if k.dtype != q.dtype:
        k = k.astype(q.dtype)
    if v.dtype != q.dtype:
        v = v.astype(q.dtype)
    block_q, block_k = _blocks(seq_q, seq_k, block_q, block_k)
    if segment_ids is None:
        q_seg = jnp.zeros((batch, seq_q), jnp.int32)
        kv_seg = jnp.zeros((batch, seq_k), jnp.int32)
    else:
        q_seg = segment_ids.astype(jnp.int32)
        kv_seg = (q_seg if kv_segment_ids is None
                  else kv_segment_ids.astype(jnp.int32))
    by_q, by_k = block_schedule(q_seg, kv_seg, block_q, block_k, bool(causal))
    return _flash_attention(q, k, v, q_seg, kv_seg, by_q, by_k, bool(causal),
                            float(sm_scale), block_q, block_k,
                            bool(interpret), bool(FLAGS.attn_pv_f32))
