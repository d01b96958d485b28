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
  - forward is a pallas kernel: grid (batch, heads, q-blocks, k-blocks) with
    the key axis STREAMED through the grid — only one (block_q x D) and one
    (block_k x D) tile is ever resident in VMEM, with the online-softmax
    carry (m, l, acc) held in VMEM scratch across the key axis.  VMEM use is
    O(block^2) at ANY sequence length (the previous design kept full-seq K/V
    resident per grid cell and hit the 16 MB scoped-vmem wall at 8192 packed
    tokens).  Pallas double-buffers the streamed tiles, so the K/V DMA for
    block j+1 overlaps the block-j matmuls; matmuls hit the MXU with
    block_q x head_dim x block_k shapes and fp32 accumulation.
  - backward is TWO pallas kernels (dK/dV with the QUERY axis streamed
    through the grid, dQ with the KEY axis streamed), each recomputing P
    blockwise from (q, k, lse) — the S x S score matrix never exists in
    either direction, and neither kernel holds a full sequence in VMEM.
    There is no plain-JAX backward: ``jax.grad`` through ``mha_reference``
    is the oracle the tests compare against.
  - causal masking skips fully-masked blocks with pl.when AND clamps the
    streamed-tile index maps, so the revisiting optimisation elides the DMA
    for blocks that would be skipped (~half the grid for causal).
  - on CPU (tests / 8-device virtual mesh) the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
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


def _seg_live(qseg_ref, kseg_ref, b):
    """Runtime block-skip predicate: packed sequences give each (q, k) block
    an id range; disjoint ranges mean no q_seg == k_seg pair exists, so the
    whole block is dead.  Conservative (overlapping ranges without an equal
    pair still compute), hence correct for ANY id assignment.  Forward and
    both backward kernels MUST use this same predicate so lse is never
    consumed by a pair the forward skipped."""
    q_sg = qseg_ref[b, :]
    k_sg = kseg_ref[b, :]
    return ((jnp.max(q_sg) >= jnp.min(k_sg)) &
            (jnp.min(q_sg) <= jnp.max(k_sg)))


def _clamped_kv_maps(causal, block_q, block_k):
    """Index maps for the streamed key-axis tiles on a (b, h, i, j) grid.
    Under causal masking, clamp j to the last live key block for q block i
    (`j*block_k < (i+1)*block_q` — the same bound the kernels' live
    predicate uses), so skipped blocks repeat the previous index and the
    revisiting optimisation elides their DMA entirely."""
    if causal:
        def kv_idx(b, h, i, j):
            return (b, h, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k),
                    0)

        def kseg_idx(b, h, i, j):
            return (0, jnp.minimum(j, ((i + 1) * block_q - 1) // block_k))
    else:
        def kv_idx(b, h, i, j):
            return (b, h, j, 0)

        def kseg_idx(b, h, i, j):
            return (0, j)
    return kv_idx, kseg_idx


def _flash_fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref,
                      lse_ref, m_scr, l_scr, acc_scr, *, sm_scale: float,
                      causal: bool, num_kb: int, pv_f32: bool):
    # q_ref: (1, 1, block_q, D); k_ref/v_ref: (1, 1, block_k, D) — the key
    # axis is the LAST grid dim, streamed; carries (m, l, acc) persist in
    # VMEM scratch across it.  qseg_ref: (B, block_q); kseg_ref: (B, block_k)
    # — full batch dim because TPU block shapes must tile (8, 128) or span
    # the whole array dim.
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    b = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: key blocks strictly after this q block are fully masked;
    # _seg_live skips cross-segment blocks at runtime
    seg_live = _seg_live(qseg_ref, kseg_ref, b)
    live = seg_live & (j * block_k < (qi + 1) * block_q) if causal \
        else seg_live

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

    @pl.when(j == num_kb - 1)
    def _finalize():
        m = jnp.max(m_scr[...], axis=1, keepdims=True)
        l = jnp.max(l_scr[...], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m + jnp.log(l)


def _dim_semantics(grid_ndim: int, interpret: bool):
    """Grid (batch, heads, blocks, streamed): all parallel but the last —
    only the streamed axis carries scratch state, so megacore may split any
    earlier dim across cores."""
    if interpret:
        return None  # interpret mode ignores TPU compiler params
    sem = ("parallel",) * (grid_ndim - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
               interpret, pv_f32=False):
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"sequence lengths ({seq_q},{seq_k}) must divide by blocks "
        f"({block_q},{block_k}) — DataFeeder pads capacity to multiples")
    # (B, S, H, D) -> (B, H, S, D) for contiguous per-head blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    num_kb = seq_k // block_k
    kv_idx, kseg_idx = _clamped_kv_maps(causal, block_q, block_k)
    grid = (batch, heads, seq_q // block_q, num_kb)
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, num_kb=num_kb, pv_f32=pv_f32)
    out_t, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_idx),
            pl.BlockSpec((batch, block_q), lambda b, h, i, j: (0, i)),
            pl.BlockSpec((batch, block_k), kseg_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_q, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        compiler_params=_dim_semantics(4, interpret),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt, q_seg, kv_seg)
    return out_t.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# Backward: pallas kernels (dK/dV then dQ), mirroring the forward's
# blocking. Reference for what they replace: the reference's hand-fused
# CUDA attention-adjacent kernels (paddle/cuda/src/*.cu) — here the win is
# recomputing P blockwise from (q, k, lse) so the S x S matrix never
# exists, with fp32 accumulation on the MXU.
# ---------------------------------------------------------------------------


def _flash_bwd_kv_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref,
                         lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                         *, sm_scale: float, causal: bool, num_qb: int,
                         pv_f32: bool):
    # grid (B, H, k-blocks, q-blocks): the QUERY axis is streamed through
    # the last grid dim; dk/dv accumulate in VMEM scratch across it.
    # k_ref/v_ref: (1, 1, block_k, D); q/do: (1, 1, block_q, D);
    # lse/delta: (1, 1, block_q, 1); qseg: (B, block_q); kseg: (B, block_k)
    block_k = k_ref.shape[2]
    block_q = q_ref.shape[2]
    b = pl.program_id(0)
    kj = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # causal: q blocks whose last row precedes this k block are fully
    # masked; _seg_live skips cross-segment blocks at runtime
    seg_live = _seg_live(qseg_ref, kseg_ref, b)
    live = seg_live & ((i + 1) * block_q > kj * block_k) if causal \
        else seg_live

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

    @pl.when(i == num_qb - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref,
                         lse_ref, delta_ref, dq_ref, dq_scr, *,
                         sm_scale: float, causal: bool, num_kb: int,
                         pv_f32: bool):
    # grid (B, H, q-blocks, k-blocks): the KEY axis is streamed through the
    # last grid dim; dq accumulates in VMEM scratch across it.
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    b = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    seg_live = _seg_live(qseg_ref, kseg_ref, b)
    live = seg_live & (j * block_k < (qi + 1) * block_q) if causal \
        else seg_live

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

    @pl.when(j == num_kb - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(res, do, *, causal, sm_scale, block_q, block_k,
                      interpret, pv_f32=False):
    q, k, v, q_seg, kv_seg, out, lse = res
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    num_qb = seq_q // block_q
    num_kb = seq_k // block_k

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32) *
                    out.transpose(0, 2, 1, 3).astype(jnp.float32),
                    axis=-1, keepdims=True)               # (B, H, Sq, 1)
    lse_t = lse[..., None]                                # (B, H, Sq, 1)

    # --- dK/dV: grid (B, H, k-blocks, q-blocks), query axis streamed ---
    if causal:
        # clamp the streamed q-tile index so fully-masked q blocks (strictly
        # before the k block) don't re-DMA; pl.when skips their compute.
        # The upper clamp to num_qb-1 covers causal cross-attention with
        # seq_k > seq_q, where (kj*block_k)//block_q can exceed the last
        # q block (the old code degraded to an out-of-range block index).
        def q_idx(b, h, kj, i):
            return (b, h, jnp.minimum(num_qb - 1,
                                      jnp.maximum(i, (kj * block_k) // block_q)),
                    0)

        def qseg_idx(b, h, kj, i):
            return (0, jnp.minimum(num_qb - 1,
                                   jnp.maximum(i, (kj * block_k) // block_q)))
    else:
        def q_idx(b, h, kj, i):
            return (b, h, i, 0)

        def qseg_idx(b, h, kj, i):
            return (0, i)

    dk_t, dv_t = pl.pallas_call(
        functools.partial(_flash_bwd_kv_kernel, sm_scale=sm_scale,
                          causal=causal, num_qb=num_qb, pv_f32=pv_f32),
        grid=(batch, heads, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, kj, i: (b, h, kj, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, kj, i: (b, h, kj, 0)),
            pl.BlockSpec((batch, block_q), qseg_idx),
            pl.BlockSpec((batch, block_k), lambda b, h, kj, i: (0, kj)),
            pl.BlockSpec((1, 1, block_q, head_dim), q_idx),
            pl.BlockSpec((1, 1, block_q, 1), q_idx),
            pl.BlockSpec((1, 1, block_q, 1), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, kj, i: (b, h, kj, 0)),
            pl.BlockSpec((1, 1, block_k, head_dim),
                         lambda b, h, kj, i: (b, h, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq_k, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch, heads, seq_k, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=_dim_semantics(4, interpret),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, q_seg, kv_seg, dot, lse_t, delta)

    # --- dQ: grid (B, H, q-blocks, k-blocks), key axis streamed ---
    kv_idx, kseg_idx = _clamped_kv_maps(causal, block_q, block_k)
    blk_q = pl.BlockSpec((1, 1, block_q, head_dim),
                         lambda b, h, i, j: (b, h, i, 0))
    blk_q1 = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))

    dq_t = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, num_kb=num_kb, pv_f32=pv_f32),
        grid=(batch, heads, num_qb, num_kb),
        in_specs=[
            blk_q,
            pl.BlockSpec((1, 1, block_k, head_dim), kv_idx),
            pl.BlockSpec((1, 1, block_k, head_dim), kv_idx),
            pl.BlockSpec((batch, block_q), lambda b, h, i, j: (0, i)),
            pl.BlockSpec((batch, block_k), kseg_idx),
            blk_q,
            blk_q1,
            blk_q1,
        ],
        out_specs=blk_q,
        out_shape=jax.ShapeDtypeStruct((batch, heads, seq_q, head_dim),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=_dim_semantics(4, interpret),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, q_seg, kv_seg, dot, lse_t, delta)

    return (dq_t.transpose(0, 2, 1, 3), dk_t.transpose(0, 2, 1, 3),
            dv_t.transpose(0, 2, 1, 3), None, None)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_attention(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q,
                     block_k, interpret, pv_f32):
    out, _ = _flash_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q,
                        block_k, interpret, pv_f32=pv_f32)
    return out


def _fwd_rule(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
              interpret, pv_f32):
    out, lse = _flash_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q,
                          block_k, interpret, pv_f32=pv_f32)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


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
    """
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    from paddle_tpu.platform.flags import FLAGS

    # The default tile edge is the largest of the ladder that divides the
    # sequence (call sites that chose their blocks — ring/ulysses
    # shard-sized tiles, tests — keep them): streaming keeps VMEM at
    # O(block^2), so big tiles are free memory-wise and each grid cell
    # amortizes its fixed cost over 16x more MXU work than a 128 tile
    # (measured: 128 tiles at seq 4096 = 32k grid cells of ~760ns overhead
    # each, dwarfing the matmuls).
    def _auto_block(seq):
        for edge in _BLOCK_LADDER:
            if seq % edge == 0:
                return edge
        return 128  # small/ragged seqs: min() below clamps to seq

    batch, seq_q = q.shape[0], q.shape[1]
    seq_k = k.shape[1]
    # the kernels feed operands to the MXU in their native dtype (no f32
    # upcast), which requires uniform q/k/v dtypes — normalize mixed-dtype
    # calls (e.g. a bf16 query against an f32 KV cache) to q's dtype here
    if k.dtype != q.dtype:
        k = k.astype(q.dtype)
    if v.dtype != q.dtype:
        v = v.astype(q.dtype)
    if block_q is None:
        block_q = _auto_block(seq_q)
    if block_k is None:
        block_k = _auto_block(seq_k)
    if segment_ids is None:
        q_seg = jnp.zeros((batch, seq_q), jnp.int32)
        kv_seg = jnp.zeros((batch, seq_k), jnp.int32)
    else:
        q_seg = segment_ids.astype(jnp.int32)
        kv_seg = (q_seg if kv_segment_ids is None
                  else kv_segment_ids.astype(jnp.int32))
    return _flash_attention(q, k, v, q_seg, kv_seg, bool(causal),
                            float(sm_scale), int(block_q), int(block_k),
                            bool(interpret), bool(FLAGS.attn_pv_f32))
