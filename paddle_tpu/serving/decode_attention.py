"""Ragged paged attention v2: ONE kernel for mixed prefill + decode.

The v1 kernel (rounds 5-9) was decode-only — grid ``(B, H, pages)``, one
query row per sequence — and chunked prefill ran as a *separate*
gather+offset-masked program interleaved at the tick level, so every
tick with in-flight prefill paid two dispatches, two softmax passes over
shared pages, and duplicate K/V HBM traffic per query head.  This
rebuild (the headline kernel of arXiv 2604.15464) folds both into one
ragged invocation:

- **Sequence-packed rows.**  The query batch is a flat ``[T, H, D]`` row
  stack: decode slots contribute one row each, in-flight prefill chunks
  contribute up to ``serving_prefill_chunk`` rows each.  A row→sequence
  map (``row_seq``) and a per-row absolute position (``qpos``, −1 for
  padding) drive ONE causal/offset mask — ``token t is visible to the
  row at position p iff t <= p`` — which subsumes decode length masking,
  in-chunk causality, and cached-prefix offsets.
- **Scalar-prefetched page tables.**  For the pallas path the rows are
  packed into blocks of :data:`BLOCK_ROWS` with one sequence per block;
  the per-block sequence id, the page tables, and the KV lengths ride in
  as scalar-prefetch operands so the K/V BlockSpec index maps chase the
  ragged page chain and DMA exactly the pages each block's sequence
  owns, page j+1's fetch overlapping page j's compute.  Dead pages are
  skipped with ``pl.when`` AND their index maps clamp to the last live
  page, so the revisiting optimisation elides the dead DMAs.
- **GQA head-group packing.**  The grid's head axis runs over KV heads,
  not query heads: a block of ``BLOCK_ROWS * group`` query rows (group =
  ``num_heads // num_kv_heads``) is packed against each K/V page load,
  so K/V HBM traffic drops by the group factor — the pool stores KV
  heads only.
- **The pool where it lies.**  The kernel's K and V operands are the
  pool's own leaves, stored ``[L, pages, page, KVH * D]``
  (``kv_cache.KVPages``) and addressed as ``[L * pages, page,
  KVH * D]`` — a merge of leading, untiled dims, free on a TPU — with
  the layer as a fourth scalar-prefetch operand: the index maps add
  ``layer * pages`` to the page id.  So the compiled serving step
  neither slices a layer out of the pool nor re-tiles it; the layer is
  a traced operand, so one lowering of the kernel serves all ``L``
  calls of a step.
- **All of a chip's KV heads in one grid cell.**  A grid step costs a
  fixed quarter of a microsecond whatever it does, and a page's
  ``(page, KVH * D)`` slab is contiguous in the pool, so a cell takes
  ``hb`` KV heads at once (:func:`heads_per_cell`: as many as fit the
  VMEM budget — all of them at the widths served so far): ONE DMA of
  the slab where there were ``hb`` strided ones, the page's liveness
  and the mask evaluated once, then the per-head online-softmax update
  for each head over its lane slice ``[:, h * D:(h + 1) * D]``.  Per
  head the arithmetic and its order over pages are those of ``hb = 1``,
  bit for bit; only the number of grid steps changes.
- **Window layers.**  With a ``window`` (static, a layer's) a row at
  position ``p`` sees token ``t`` iff ``p - window < t <= p``, and the
  page table is read as a RING: page ``a`` of a sequence lives at entry
  ``a mod width`` (``kv_cache.WindowRing``; a full-width table never
  wraps).  The page axis of the grid then starts at the block's first
  live page: two more scalar-prefetch operands give each row block its
  first and last live page (from its rows' positions), the axis has only
  as many steps as a block's rows can see pages, and a page below every
  row's window is neither fetched nor a grid step.
- **int8 pages, dequant in-register.**  Quantized pools ship per-token,
  per-kv-head f32 scales next to the int8 pages; the kernel (and the
  gather fallback — see ``kv_cache.dequantize_kv``, the ONE shared
  rule) dequantizes in-register, so HBM reads stay 1 byte/element.

Two paths with identical semantics, selected by :func:`attention_path`
— the single dispatch gate every paged-attention call routes through:

- **Pallas kernel**: grid ``(row_blocks, kv_heads / hb, pages)``,
  online-softmax carry (m, l, acc) per head in VMEM scratch across the
  page axis.
- **Reference path** (CPU/interpreter fallback and the parity oracle):
  page-table gather + masked softmax in f32 — no new math to trust,
  reading the SAME stored (possibly quantized) values.

Decode rows are bandwidth-bound (a [G, D] x [page, D] product per
page and head), so the kernel's job there is DMA shape and few grid
steps; prefill rows add real MXU work that v1 paid in a second
dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.attention import DEFAULT_MASK_VALUE, _dim_semantics
from paddle_tpu.ops.kernel_util import interpret_default as _interpret_default
from paddle_tpu.platform.enforce import enforce_that
from paddle_tpu.serving.kv_cache import (KVPages, dequantize_kv,
                                         kv_pool_specs, layer_pages,
                                         quantize_kv, window_pages)

_LANES = 128     # lane width of the (rows, _LANES) m/l scratch carries
BLOCK_ROWS = 8   # sublane row-block granularity of the sequence packing

# the int8 parity harness's logit-error bound: attention output feeds
# logits through bounded linear maps, so a relative output-error bound
# IS a logit-error bound up to the model's Lipschitz constant.  The
# per-token amax/127 scheme lands well under 2% on gaussian K/V; 5%
# leaves slack for adversarial value distributions without letting a
# broken quant path (wrong scale axis, missing dequant) slip through.
QUANT_DRIFT_BOUND = 0.05


# ---------------------------------------------------------------------------
# Dispatch gate
# ---------------------------------------------------------------------------

def attention_path(head_dim: int, page_size: int, *,
                   num_heads: Optional[int] = None,
                   num_kv_heads: Optional[int] = None,
                   quantized: bool = False,
                   use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> str:
    """THE chooser: every paged-attention dispatch (ragged kernel,
    engine step builder) routes through this one gate,
    so odd head dims / tiny pages / mismatched head groups fall back to
    the reference path at a single point instead of per-call-site
    guesswork.  Returns ``"kernel"`` or ``"reference"``.

    Native-compile gate: the kernel's tiles are (page, D) and
    (rows*group, D) — lane-aligned D and sublane-aligned pages avoid
    layout changes on real hardware; int8 additionally wants lane-aligned
    pages for its (page,) scale vectors.  ``use_kernel`` (not None)
    forces the answer either way (tests run the kernel under
    ``interpret=True``)."""
    if use_kernel is not None:
        return "kernel" if use_kernel else "reference"
    if interpret is None:
        interpret = _interpret_default()
    if interpret:
        return "reference"
    if head_dim % _LANES != 0 or page_size % 8 != 0:
        return "reference"
    if quantized and page_size % _LANES != 0:
        return "reference"
    if num_heads and num_kv_heads and num_heads % num_kv_heads != 0:
        return "reference"
    return "kernel"


# What the double-buffered K and V slabs (and scale blocks) of one grid
# cell may take: half of the 16 MiB of VMEM a Mosaic kernel is scoped
# to by default on a TPU v5e, the rest left to q, out, the softmax
# carries and the (rows, page) scores of the body.
_KV_VMEM_BUDGET = 8 << 20


def heads_per_cell(num_kv_heads: int, page_size: int, head_dim: int,
                   kv_itemsize: int, quantized: bool = False) -> int:
    """``hb``: how many of the (shard's) KV heads one grid cell of the
    ragged kernel takes — the largest divisor of ``num_kv_heads`` whose
    K and V tiles ``(page, hb * D)``, double-buffered, fit
    :data:`_KV_VMEM_BUDGET` beside the two ``(page, KVH)`` scale blocks
    an int8 pool ships whole.  A pure function of shapes: 8 and 16
    float32 heads of 128 at page 128 fold whole (2 and 4 MiB); a slab
    that does not fit falls to a divisor, down to one head a cell."""
    scales = 2 * 2 * page_size * num_kv_heads * 4 if quantized else 0
    per_head = 2 * 2 * page_size * head_dim * kv_itemsize
    for hb in range(num_kv_heads, 1, -1):
        if num_kv_heads % hb == 0 and \
                hb * per_head + scales <= _KV_VMEM_BUDGET:
            return hb
    return 1


# ---------------------------------------------------------------------------
# Reference path (oracle + CPU fallback)
# ---------------------------------------------------------------------------

def ragged_paged_attention_reference(q, k_pages, v_pages, page_table,
                                     kv_lens, row_seq, qpos, *,
                                     k_scale=None, v_scale=None,
                                     sm_scale: Optional[float] = None,
                                     window: Optional[int] = None):
    """Gather-then-mask oracle for the ragged kernel.

    q: [T, H, D] — the sequence-packed row stack (decode rows AND
    prefill-chunk rows); k_pages/v_pages: [num_pages, page, H_kv, D]
    (ONE layer's pool slice, possibly int8 with ``k_scale``/``v_scale``
    [num_pages, page, H_kv]); page_table: [S, max_pages_per_seq] int32;
    kv_lens: [S] int32 — valid cached tokens per sequence AFTER this
    step's writes; row_seq: [T] int32 row→sequence map; qpos: [T] int32
    per-row absolute position (−1 = padded row).  Returns [T, H, D].

    Row r attends over tokens ``0..qpos[r]`` of sequence ``row_seq[r]``
    — decode length masking, in-chunk causality and cached-prefix
    offsets are all this one inequality.  Padded rows return an
    arbitrary finite value (fully-masked softmax degenerates to
    uniform); callers never read them.

    ``window`` (a layer's, static): row r attends over tokens ``qpos[r] -
    window + 1 .. qpos[r]`` only, and ``page_table`` is a ring (page
    ``a`` of a sequence at entry ``a mod width``): a row gathers the few
    pages its window can touch, from its first live one."""
    t, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    pm = page_table.shape[1]
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    if window is None:
        pt = page_table[row_seq]                   # [T, Pm]
    else:
        # the row's own pages: from the first its window reaches
        pm, ring = window_pages(window, 1, page, pm), pm
        first = jnp.maximum(qpos - window + 1, 0) // page        # [T]
        at = first[:, None] + jnp.arange(pm, dtype=jnp.int32)    # [T, pm]
        pt = page_table[row_seq[:, None], at % ring]
    k = k_pages[pt]                                # [T, Pm, page, KVH, D]
    v = v_pages[pt]
    if k_scale is not None:
        k = dequantize_kv(k, k_scale[pt])
        v = dequantize_kv(v, v_scale[pt])
    k = k.reshape(t, pm * page, kvh, d).astype(jnp.float32)
    v = v.reshape(t, pm * page, kvh, d).astype(jnp.float32)
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)        # GQA head replication
        v = jnp.repeat(v, h // kvh, axis=2)
    if window is None:
        tok = jnp.arange(pm * page, dtype=jnp.int32)
        live = ((tok[None, :] <= qpos[:, None]) &
                (tok[None, :] < kv_lens[row_seq][:, None]))
    else:
        tok = (at[:, :, None] * page + jnp.arange(page, dtype=jnp.int32)
               ).reshape(t, pm * page)
        live = ((tok <= qpos[:, None]) & (tok > qpos[:, None] - window) &
                (tok < kv_lens[row_seq][:, None]))
    s = jnp.einsum("thd,tkhd->thk", q.astype(jnp.float32), k) * sm_scale
    s = jnp.where(live[:, None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("thk,tkhd->thd", p, v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _ragged_kernel(blk_seq_ref, pt_ref, len_ref, layer_ref, *rest,
                   page_size: int, num_pb: int, hb: int, sm_scale: float,
                   quantized: bool, window: Optional[int] = None):
    # grid (row_blocks, kv_head_groups, pages-per-seq), ``hb`` KV heads
    # a group: the page axis is streamed; every head's (m, l, acc)
    # persist in VMEM scratch across it.  blk_seq/pt/len are the
    # scalar-prefetched block→sequence map [NB], page table [S, Pm] and
    # KV lengths [S] (SMEM); layer_ref [1] rides with them for the
    # index maps alone (the body never reads it).  qpos_ref:
    # (1, RBG, 1) — per-score-row absolute positions, already
    # group-expanded, as a sublane column so the mask broadcasts over
    # the (RBG, page) scores without a layout change.  q_ref/o_ref: (hb, 1, RBG, D); k_ref/v_ref:
    # (1, page, hb * D) — the group's lane slab of one page, contiguous
    # in the pool (the whole page when hb is all heads), head h of the
    # group at lanes [h * D, (h + 1) * D); quantized adds ks/vs
    # (1, page, KVH) scale blocks (all KV heads: a (page, 1) block is
    # not a legal TPU tile).  Scratch: m/l (hb, RBG, LANES), acc
    # (hb, RBG, D).  The body tests the page's liveness and builds the
    # mask once, then runs the per-head update hb times, unrolled.
    # With a ``window``, two more scalar-prefetch operands lead ``rest``:
    # the block's first and last live page [NB] (its page axis starts at
    # the first: step j is page ``first + j``), and the mask gains the
    # window's lower bound.
    first_ref = last_ref = None
    if window is not None:
        first_ref, last_ref, *rest = rest
    qpos_ref, q_ref, k_ref, v_ref, *rest = rest
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ib = pl.program_id(0)
    hg = pl.program_id(1)
    j = pl.program_id(2)
    rbg, d = q_ref.shape[2:]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if window is None:
        at = j
        n = len_ref[blk_seq_ref[ib]]
        live = j * page_size < n
    else:
        at = first_ref[ib] + j
        live = at <= last_ref[ib]

    @pl.when(live)
    def _compute():
        tok = at * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rbg, page_size), 1)
        # ONE inequality is the whole mask: causal for prefill rows,
        # length for decode rows, everything for padded rows (qpos −1)
        seen = tok <= qpos_ref[0]
        if window is not None:
            seen = seen & (tok > qpos_ref[0] - window)
        if quantized:
            # in-register dequant: HBM traffic stays 1 byte/element.
            # A KV head's scale column is picked out of the
            # (page, KVH) block with a masked lane reduction.
            ksc, vsc = ks_ref[0], vs_ref[0]
            lane = jax.lax.broadcasted_iota(jnp.int32, ksc.shape, 1)

            def head_scale(sc, head):
                return jnp.sum(jnp.where(lane == head, sc, 0.0),
                               axis=1, keepdims=True)      # (page, 1)
        for h in range(hb):
            q = q_ref[h, 0]                        # (RBG, D)
            kb = k_ref[0, :, h * d:(h + 1) * d]    # (page, D)
            vb = v_ref[0, :, h * d:(h + 1) * d]
            if quantized:
                kb = kb.astype(jnp.float32) * head_scale(ksc, hg * hb + h)
                vb = vb.astype(jnp.float32) * head_scale(vsc, hg * hb + h)
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale                       # (RBG, page)
            s = jnp.where(seen, s, DEFAULT_MASK_VALUE)

            m_prev = jnp.max(m_scr[h], axis=1, keepdims=True)
            l_prev = jnp.max(l_scr[h], axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == num_pb - 1)
    def _finalize():
        for h in range(hb):
            l = jnp.max(l_scr[h], axis=1, keepdims=True)
            l = jnp.where(l == 0.0, 1.0, l)  # length-0 rows -> zeros, not NaN
            o_ref[h, 0] = (acc_scr[h] / l).astype(o_ref.dtype)


def _ragged_pallas(q, k_pool, v_pool, k_scale, v_scale, layer, page_table,
                   kv_lens, row_seq, qpos, sm_scale, interpret: bool,
                   window: Optional[int] = None):
    """Kernel-path entry, on the STORED pool (``[L, pages, page,
    KVH * D]``) and a layer index.  REQUIRES block-uniform packing: T a
    multiple of :data:`BLOCK_ROWS` and every aligned block of rows
    belonging to ONE sequence (callers pad each sequence's rows to the
    block size — decode slots to one block, chunks to whole blocks).
    The block map is read as ``row_seq[::BLOCK_ROWS]``; rows that
    violate uniformity would silently attend over the wrong pages, so
    the engine owns the packing and tests pin it against the reference
    path.  With a ``window`` the live rows of a block lie within
    :data:`BLOCK_ROWS` consecutive positions (as the engine packs them: a
    chunk's rows follow each other, a slot's verify rows too), which is
    what bounds the pages a block can see."""
    t, h, d = q.shape
    page, kvh = k_pool.shape[2], k_pool.shape[3] // d
    enforce_that(t % BLOCK_ROWS == 0,
                 f"ragged kernel rows ({t}) must pack to BLOCK_ROWS "
                 f"({BLOCK_ROWS})", context="serving")
    enforce_that(h % kvh == 0, f"num_heads ({h}) must be a multiple of "
                 f"num_kv_heads ({kvh})", context="serving")
    hb = heads_per_cell(kvh, page, d, k_pool.dtype.itemsize,
                        k_scale is not None)
    # the layer is an OPERAND, never a static argument: one trace and
    # one lowering of the kernel then serve every layer of a step
    return _ragged_call(q, k_pool, v_pool, k_scale, v_scale,
                        jnp.asarray(layer, jnp.int32).reshape(1), page_table,
                        kv_lens, row_seq, qpos, hb=hb, sm_scale=sm_scale,
                        interpret=interpret, window=window)


# jitted on its own so that a step program of L layers traces and lowers
# the kernel once and calls it L times: the body is unrolled over the
# cell's heads, and Pallas lowers in Python in every process, persistent
# compile cache or not — per layer that is seconds of set-up
@functools.partial(jax.jit, static_argnames=("hb", "sm_scale", "interpret",
                                             "window"))
def _ragged_call(q, k_pool, v_pool, k_scale, v_scale, layer, page_table,
                 kv_lens, row_seq, qpos, *, hb: int, sm_scale: float,
                 interpret: bool, window: Optional[int] = None):
    t, h, d = q.shape
    _, pages, page, lanes = k_pool.shape
    kvh = lanes // d
    pm = page_table.shape[1]
    g = h // kvh
    nb = t // BLOCK_ROWS
    rbg = BLOCK_ROWS * g
    quantized = k_scale is not None

    blk_seq = row_seq.reshape(nb, BLOCK_ROWS)[:, 0].astype(jnp.int32)
    qpos_rows = jnp.repeat(qpos.astype(jnp.int32).reshape(nb, BLOCK_ROWS),
                           g, axis=1)[..., None]          # (NB, RBG, 1)
    # [T, H, D] -> [KVH, NB, RB*G, D]: each block packs its G query
    # heads per KV head next to each other, so one K/V page load feeds
    # the whole head group
    q5 = q.reshape(nb, BLOCK_ROWS, kvh, g, d).transpose(2, 0, 1, 3, 4)
    q5 = q5.reshape(kvh, nb, rbg, d)
    # the pool [L, P, page, KVH*D] addressed as [L*P, page, KVH*D]: the
    # two tiled dims stay as they are, so this is no copy on a TPU, and
    # page p of the layer is row ``layer * P + p``.  The KV heads of
    # group hg are lanes [hg*hb*D, (hg+1)*hb*D) of that page's
    # (page, KVH*D) slab, so the index map addresses them as
    # (row, 0, hg) with a legal (page, hb*D) tile and no transpose.
    kt = k_pool.reshape(-1, page, lanes)
    vt = v_pool.reshape(-1, page, lanes)
    pt = page_table.astype(jnp.int32)
    ln = kv_lens.astype(jnp.int32)

    prefetch = [blk_seq, pt, ln, layer]
    steps = pm
    if window is not None:
        # each block's first and last live page, from its rows' positions
        # (a block with no live row: last < first, nothing is visited);
        # the page axis starts at the first and is only as long as a
        # block's rows can see pages
        qb = qpos.astype(jnp.int32).reshape(nb, BLOCK_ROWS)
        real = qb >= 0
        lo = jnp.min(jnp.where(real, qb, jnp.iinfo(jnp.int32).max), axis=1)
        hi = jnp.minimum(jnp.max(qb, axis=1), ln[blk_seq] - 1)
        some = jnp.any(real, axis=1) & (hi >= 0)
        first = jnp.where(some, jnp.maximum(lo - window + 1, 0) // page, 0)
        last = jnp.where(some, hi // page, -1)
        prefetch += [first.astype(jnp.int32), last.astype(jnp.int32)]
        steps = window_pages(window, BLOCK_ROWS, page, pm)

    # TPU block shapes must end in (8k, 128k) or the array's own last
    # two dims — every spec below is written to that rule

    def qpos_idx(ib, hg, j, *refs):
        return (ib, 0, 0)

    def q_idx(ib, hg, j, *refs):
        return (hg, ib, 0, 0)

    def live_row(ib, j, blk_ref, pt_ref, len_ref, layer_ref, *bounds):
        # clamp dead pages (j past the block's sequence's last live
        # page) to the last live one so their DMA is elided by
        # revisiting; pl.when skips their compute.  max(len-1, 0) keeps
        # length-0 sequences legal.
        seq = blk_ref[ib]
        if bounds:
            # the table is a ring: page ``a`` at entry ``a mod width``
            first_ref, last_ref = bounds
            at = jnp.minimum(first_ref[ib] + j,
                             jnp.maximum(last_ref[ib], first_ref[ib]))
            return layer_ref[0] * pages + pt_ref[seq, at % pm]
        last = jnp.maximum(len_ref[seq] - 1, 0) // page
        return layer_ref[0] * pages + pt_ref[seq, jnp.minimum(j, last)]

    def kv_idx(ib, hg, j, *refs):
        return (live_row(ib, j, *refs), 0, hg)

    def scale_idx(ib, hg, j, *refs):
        return (live_row(ib, j, *refs), 0, 0)

    in_specs = [
        pl.BlockSpec((1, rbg, 1), qpos_idx),
        pl.BlockSpec((hb, 1, rbg, d), q_idx),
        pl.BlockSpec((1, page, hb * d), kv_idx),
        pl.BlockSpec((1, page, hb * d), kv_idx),
    ]
    args = [qpos_rows, q5, kt, vt]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, kvh), scale_idx),
                     pl.BlockSpec((1, page, kvh), scale_idx)]
        args += [k_scale.reshape(-1, page, kvh),
                 v_scale.reshape(-1, page, kvh)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(nb, kvh // hb, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((hb, 1, rbg, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((hb, rbg, _LANES), jnp.float32),
            pltpu.VMEM((hb, rbg, _LANES), jnp.float32),
            pltpu.VMEM((hb, rbg, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, page_size=page, num_pb=steps,
                               hb=hb, sm_scale=sm_scale, quantized=quantized,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, nb, rbg, d), q.dtype),
        compiler_params=_dim_semantics(3, interpret),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*prefetch, *args)
    out = out.reshape(kvh, nb, BLOCK_ROWS, g, d).transpose(1, 2, 0, 3, 4)
    return out.reshape(t, h, d)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _reference_on_layer(q, k_pool, v_pool, k_scale, v_scale, layer, *rest,
                        sm_scale, window=None):
    """The (row-blocked) reference path on one layer of a stored pool."""
    k, v, ks, vs = layer_pages(
        KVPages(k_pool, v_pool, k_scale, v_scale, head_dim=q.shape[-1]),
        layer)
    return _ragged_reference_blocked(q, k, v, *rest, k_scale=ks, v_scale=vs,
                                     sm_scale=sm_scale, window=window)


def _pool_dims(q, k_pool, v_pool, k_scale):
    """``(page, KVH)`` of the stored pool the public entries take,
    refusing an operand that cannot be one for this ``q``.  (Shapes
    cannot tell one layer's f32 ``[pages, page, KVH, D]`` from a
    one-head pool ``[L, pages, page, D]``: the required ``layer`` is
    what asks the caller which it holds.)"""
    d = q.shape[-1]
    enforce_that(
        k_pool.ndim == 4 and k_pool.shape == v_pool.shape
        and k_pool.shape[3] % d == 0
        and (k_scale is None or
             k_scale.shape == k_pool.shape[:3] + (k_pool.shape[3] // d,)),
        f"ragged attention takes the STORED pool [L, pages, page, KVH * D] "
        f"(int8 scales [L, pages, page, KVH]) and a layer index, not one "
        f"layer's [pages, page, KVH, D]; got k {tuple(k_pool.shape)}, "
        f"v {tuple(v_pool.shape)}, scales "
        f"{None if k_scale is None else tuple(k_scale.shape)} for head "
        f"dim {d}", context="serving")
    return k_pool.shape[2], k_pool.shape[3] // d


def ragged_paged_attention(q, k_pool, v_pool, page_table, kv_lens,
                           row_seq, qpos, *, layer, k_scale=None,
                           v_scale=None, sm_scale: Optional[float] = None,
                           use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None):
    """Ragged paged attention over a sequence-packed mixed batch (see
    :func:`ragged_paged_attention_reference` for the semantics).

    ``k_pool``/``v_pool`` are the WHOLE stored pool ``[L, pages, page,
    KVH * D]`` (``kv_cache.KVPages``' leaves; scales ``[L, pages, page,
    KVH]``) and ``layer`` (an int or a traced scalar) the layer to
    attend over, so that the kernel reads the pool where it lies.  One
    layer's ``[pages, page, KVH, D]`` is the reference's operand
    (``kv_cache.layer_pages``), not this entry's.

    ``use_kernel=None`` auto-selects through :func:`attention_path`; the
    kernel additionally requires block-uniform :data:`BLOCK_ROWS`
    packing (the engine's packer guarantees it).  A kernel that was
    chosen runs or raises — it never degrades to the reference path.

    ``window`` (static; a window layer's): a row sees the last ``window``
    tokens up to its own, ``page_table`` is read as a ring, and pages
    below a block's windows are neither fetched nor visited (the module
    doc)."""
    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    page, kvh = _pool_dims(q, k_pool, v_pool, k_scale)
    path = attention_path(q.shape[-1], page, num_heads=q.shape[1],
                          num_kv_heads=kvh, quantized=k_scale is not None,
                          use_kernel=use_kernel, interpret=interpret)
    if path == "kernel":
        return _ragged_pallas(q, k_pool, v_pool, k_scale, v_scale, layer,
                              page_table.astype(jnp.int32),
                              kv_lens.astype(jnp.int32),
                              row_seq.astype(jnp.int32),
                              qpos.astype(jnp.int32),
                              float(sm_scale), bool(interpret),
                              window=None if window is None else int(window))
    return _reference_on_layer(q, k_pool, v_pool, k_scale, v_scale, layer,
                               page_table, kv_lens, row_seq, qpos,
                               sm_scale=sm_scale, window=window)


def ragged_paged_attention_tp(mesh, axis, q, k_pool, v_pool, page_table,
                              kv_lens, row_seq, qpos, *, layer,
                              k_scale=None, v_scale=None,
                              sm_scale: Optional[float] = None,
                              use_kernel: Optional[bool] = None,
                              interpret: Optional[bool] = None):
    """Tensor-parallel ragged attention: the pallas kernel wrapped in a
    ``shard_map`` over the ``axis`` (``model``) mesh dim.

    Heads are embarrassingly parallel in attention, so each chip runs
    the UNCHANGED kernel on its local slice — q ``[T, H/TP, D]`` against
    its shard of the stored pool, ``[L, P, page, (H_kv/TP) * D]``: the
    lanes of its own heads, passed through whole with the layer index
    (scales ride along) — and no collective crosses the region: the
    psum lives downstream in the row-parallel output projection,
    exactly the megatron pattern.  A bare ``pallas_call`` under GSPMD
    would instead force the sharded operands replicated (XLA cannot
    partition a custom kernel), which is why the TP engine routes its
    kernel path through here.  The GQA group factor is shard-invariant
    (``(H/TP) / (H_kv/TP) == H/H_kv``), so head-group packing is
    untouched.  Operands as in :func:`ragged_paged_attention`.

    Dispatch routes through :func:`attention_path` like every other
    entry point (the per-SHARD head counts decide): shapes the chooser
    rejects — odd head dims, tiny pages — fall back to the plain
    reference path, which needs no ``shard_map`` because GSPMD
    partitions its gathers/einsums over the head dim natively."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if sm_scale is None:
        sm_scale = float(q.shape[-1]) ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    tp = int(mesh.shape[axis])
    page, kvh = _pool_dims(q, k_pool, v_pool, k_scale)
    path = attention_path(q.shape[-1], page, num_heads=q.shape[1] // tp,
                          num_kv_heads=kvh // tp,
                          quantized=k_scale is not None,
                          use_kernel=use_kernel, interpret=interpret)
    if path != "kernel":
        return _reference_on_layer(q, k_pool, v_pool, k_scale, v_scale,
                                   layer, page_table, kv_lens, row_seq, qpos,
                                   sm_scale=sm_scale)
    head = P(None, axis, None)
    pool = P(*kv_pool_specs(axis))
    repl = P()
    in_specs = [head, pool, pool, repl, repl, repl, repl, repl]
    if k_scale is not None:
        in_specs += [pool, pool]

    def local(qs, ks, vs, lyr, pt, ln, rs, qp, *scales):
        kss, vss = scales if scales else (None, None)
        return _ragged_pallas(qs, ks, vs, kss, vss, lyr, pt, ln, rs, qp,
                              float(sm_scale), bool(interpret))

    fn = shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=head, check_vma=False)
    args = [q, k_pool, v_pool, jnp.asarray(layer, jnp.int32),
            page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
            row_seq.astype(jnp.int32), qpos.astype(jnp.int32)]
    if k_scale is not None:
        args += [k_scale, v_scale]
    return fn(*args)


_REF_ROW_BLOCK = 64   # fallback row-block: bounds the per-row K/V gather


def _ragged_reference_blocked(q, k_pages, v_pages, page_table, kv_lens,
                              row_seq, qpos, k_scale=None, v_scale=None,
                              sm_scale=None, block: int = _REF_ROW_BLOCK,
                              window: Optional[int] = None):
    """The reference path evaluated in row blocks.  The dumb oracle
    gathers each row's whole page chain ([T, Pm, page, H_kv, D]) — fine
    for tests, but as the ENGINE's fallback a 256-row prefill chunk
    would materialize 256 copies of its sequence's K/V where v1's chunk
    program shared one.  Mapping the oracle over fixed row blocks
    bounds the transient to ``block`` copies with identical results
    (rows are independent); the pallas path owns big shapes, this owns
    big-ish fallbacks."""
    t = q.shape[0]
    if t <= block:
        return ragged_paged_attention_reference(
            q, k_pages, v_pages, page_table, kv_lens, row_seq, qpos,
            k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
            window=window)
    pad = (-t) % block
    qp_ = jnp.concatenate([q, jnp.zeros((pad,) + q.shape[1:], q.dtype)]) \
        if pad else q
    rs_ = jnp.concatenate([row_seq, jnp.zeros((pad,), row_seq.dtype)]) \
        if pad else row_seq
    pp_ = jnp.concatenate([qpos, jnp.full((pad,), -1, qpos.dtype)]) \
        if pad else qpos

    def body(args):
        qb, rb, pb = args
        return ragged_paged_attention_reference(
            qb, k_pages, v_pages, page_table, kv_lens, rb, pb,
            k_scale=k_scale, v_scale=v_scale, sm_scale=sm_scale,
            window=window)

    h, d = q.shape[1], q.shape[2]
    out = jax.lax.map(body, (qp_.reshape(-1, block, h, d),
                             rs_.reshape(-1, block),
                             pp_.reshape(-1, block)))
    return out.reshape(-1, h, d)[:t]


def quant_parity_error(q, k_pages, v_pages, page_table, kv_lens, row_seq,
                       qpos, *, sm_scale: Optional[float] = None) -> float:
    """The int8 parity harness: max relative error the quantization
    adds to ragged attention output, measured f32-pages vs the SAME
    pages int8-roundtripped through :func:`~kv_cache.quantize_kv` (the
    identical write path the engine uses).  Padded rows are excluded.
    Host-syncs by design — this is a test/CI harness, not a tick op."""
    import numpy as np
    out32 = np.asarray(ragged_paged_attention_reference(
        q, k_pages, v_pages, page_table, kv_lens, row_seq, qpos,
        sm_scale=sm_scale))
    kq, ks = quantize_kv(k_pages)
    vq, vs = quantize_kv(v_pages)
    out8 = np.asarray(ragged_paged_attention_reference(
        q, kq, vq, page_table, kv_lens, row_seq, qpos,
        k_scale=ks, v_scale=vs, sm_scale=sm_scale))
    real = np.asarray(qpos) >= 0
    denom = max(float(np.abs(out32[real]).max()), 1e-20)
    return float(np.abs(out8[real] - out32[real]).max()) / denom


def check_quant_drift(q, k_pages, v_pages, page_table, kv_lens, row_seq,
                      qpos, *, bound: float = QUANT_DRIFT_BOUND,
                      sm_scale: Optional[float] = None) -> float:
    """Assert the harness error stays under ``bound``; the failure
    message carries the literal ``QUANT-DRIFT`` tag tools_tier1.sh
    greps into its exit-code ladder (exit 7), so a quantization
    regression anywhere in the suite is a loud, distinct failure."""
    err = quant_parity_error(q, k_pages, v_pages, page_table, kv_lens,
                             row_seq, qpos, sm_scale=sm_scale)
    if err > bound:
        raise AssertionError(
            f"QUANT-DRIFT: int8 KV parity error {err:.4f} exceeds the "
            f"logit-error bound {bound:.4f}")
    return err


def expand_decode_rows(q, qpos, rows_per_seq: int = 1):
    """Pad per-sequence decode/verify rows to whole :data:`BLOCK_ROWS`
    blocks — THE one copy of the kernel's one-sequence-per-block
    packing for decode rows.

    ``q`` is ``[B * rows_per_seq, H, D]`` sequence-major: sequence
    ``i`` owns rows ``i*rows_per_seq .. (i+1)*rows_per_seq - 1``
    (plain decode passes 1 row per sequence; a speculative verify
    passes ``k+1``).  Each sequence's rows pad up to
    ``ceil(rows_per_seq / BLOCK_ROWS) * BLOCK_ROWS`` rows (padding
    qpos −1), so every aligned block stays single-sequence no matter
    the speculation depth.  Returns ``(q_expanded, row_seq,
    qpos_expanded)``; callers slice results back by reshaping to
    ``[B, padded_rows, ...]`` and taking ``[:, :rows_per_seq]`` (for
    ``rows_per_seq == 1`` that is the historical ``[::BLOCK_ROWS]``)."""
    rps = int(rows_per_seq)
    bt, h, d = q.shape
    b = bt // rps
    rbk = -(-rps // BLOCK_ROWS) * BLOCK_ROWS
    row_seq = jnp.repeat(jnp.arange(b, dtype=jnp.int32), rbk)
    if rbk == rps:
        return q, row_seq, qpos.astype(jnp.int32)
    pad = rbk - rps
    qe = jnp.pad(q.reshape(b, rps, h, d),
                 ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b * rbk, h, d)
    qp = jnp.pad(qpos.astype(jnp.int32).reshape(b, rps),
                 ((0, 0), (0, pad)),
                 constant_values=-1).reshape(b * rbk)
    return qe, row_seq, qp
