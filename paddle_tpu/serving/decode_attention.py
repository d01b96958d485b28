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
  contribute up to ``prefill_chunk`` rows each.  A row→sequence
  map (``row_seq``) and a per-row absolute position (``qpos``, −1 for
  padding) drive ONE causal/offset mask — ``token t is visible to the
  row at position p iff t <= p`` — which subsumes decode length masking,
  in-chunk causality, and cached-prefix offsets.
- **A walk of live visits.**  For the pallas path the rows are packed so
  that every aligned :data:`BLOCK_ROWS` rows belong to one sequence, and
  stand in RESIDENT blocks of two heights in the one call: a decoding
  slot's rows in a short block of ``BLOCK_ROWS`` (its few rows ride along
  while its context's pages stream past: the fetch sets the pace), the
  rows of the prefill bucket in TALL blocks of :func:`tall_block_rows`
  (64 rows at GQA groups of 6 and 8, 128 at group 1), so that
  a page's K/V slab is fetched once for all the rows a chunk has in the
  block and the two products run with hundreds of rows on the MXU.  The
  grid's streamed axis is not a rectangle of pages but a SCHEDULE of
  visits (:func:`visit_schedule`), made in XLA from the tick's own arrays
  (``row_seq``, ``qpos``, ``kv_lens``, the page table) and
  scalar-prefetched: a visit is (resident block, page of one sequence,
  first / last flags), a block lists the pages its live rows can see and
  no other (none past a sequence's length, none above a chunk block's
  last row), and the axis' extent is the traced number of visits.  The
  index maps take the block and the pool page from the schedule, page
  j+1's fetch overlapping page j's compute; the kind of block a visit
  does not work on stands still, so nothing is fetched for it.  A tall
  block may hold rows of several sequences (chunks start at any multiple
  of ``BLOCK_ROWS``): it works on one sequence a visit, the other rows
  masked and left as they are.
- **GQA head-group packing.**  The grid's head axis runs over KV heads,
  not query heads: a resident block's rows times ``group`` query heads
  (``num_heads // num_kv_heads``) are packed against each K/V page load,
  so K/V HBM traffic drops by the group factor — the pool stores KV
  heads only.
- **The pool where it lies.**  The kernel's K and V operands are the
  pool's own leaves, stored ``[L, pages, page, KVH * D]``
  (``kv_cache.KVPages``) and addressed as ``[L * pages, page,
  KVH * D]`` — a merge of leading, untiled dims, free on a TPU — with
  the layer riding with the scalar-prefetch operands: the index maps add
  ``layer * pages`` to the page id.  So the compiled serving step
  neither slices a layer out of the pool nor re-tiles it; the layer is
  a traced operand, so one lowering of the kernel serves all ``L``
  calls of a step.
- **All of a chip's KV heads in one grid cell.**  A grid step costs a
  fixed quarter of a microsecond whatever it does, and a page's
  ``(page, KVH * D)`` slab is contiguous in the pool, so a cell takes
  ``hb`` KV heads at once (:func:`heads_per_cell`: as many as fit the
  VMEM budget — all of them at the widths served so far): ONE DMA of
  the slab where there were ``hb`` strided ones, the mask evaluated
  once, then the per-head online-softmax update
  for each head over its lane slice ``[:, h * D:(h + 1) * D]``.  Per
  head the arithmetic and its order over pages are those of ``hb = 1``,
  bit for bit; only the number of grid steps changes.
- **Window layers.**  With a ``window`` (static, a layer's) a row at
  position ``p`` sees token ``t`` iff ``p - window < t <= p``, and the
  page table is read as a RING: page ``a`` of a sequence lives at entry
  ``a mod width`` (``kv_cache.WindowRing``; a full-width table never
  wraps).  A block's visits of a sequence then start at the first page
  the window of its lowest row reaches: a page below every row's window
  is neither fetched nor a grid step.
- **int8 pages, dequant in-register.**  Quantized pools ship per-token,
  per-kv-head f32 scales next to the int8 pages; the kernel (and the
  gather fallback — see ``kv_cache.dequantize_kv``, the ONE shared
  rule) dequantizes in-register, so HBM reads stay 1 byte/element.

Two paths with identical semantics, selected by :func:`attention_path`
— the single dispatch gate every paged-attention call routes through:

- **Pallas kernel**: grid ``(kv_heads / hb, visits)``, online-softmax
  carry (m, l, acc) per head in VMEM scratch from a resident block's
  first visit to its last.
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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.attention import DEFAULT_MASK_VALUE
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
# The walk: a schedule of live (resident row block, page) visits
# ---------------------------------------------------------------------------

# a visit's flags (``Visits.flags``): it is its resident block's first
# (the carries start), its last (the block is written), it computes (a
# block nobody's row can see a page of keeps ONE visit that does not, so
# that its output is still written, as zeros), its block is a tall one
_FIRST, _LAST, _COMPUTE, _TALL = 1, 2, 4, 8


class Visits(NamedTuple):
    """The kernel's walk, one entry a grid step (int32 ``[extent]``; the
    first ``count`` are steps, the tail is never run).  ``block``: the
    resident block the step works on, the short blocks numbered before the
    tall ones (the index maps hold the kind it does not work on still:
    nothing is fetched for it); ``at``: which page of its sequence the
    step streams (the tokens' positions; the index maps look the pool's
    page up in the page table); ``seq``: the sequence (a tall block may
    hold rows of several, and works on one a visit)."""

    flags: jax.Array
    block: jax.Array
    at: jax.Array
    seq: jax.Array
    count: jax.Array               # int32 scalar: the steps of the walk


class Walk(NamedTuple):
    """All a kernel call needs of a tick's rows: the schedule of visits
    and, as sublane columns (``[blocks, rows * group, 1]``: a score row's
    value beside its (rows, page) scores without a layout change), the
    positions of the short blocks' rows, and the positions and sequences
    of the tall blocks' (a kind the call has no block of: no rows)."""

    visits: Visits
    short_pos: jax.Array
    tall_pos: jax.Array
    tall_seq: jax.Array


def _most_pages(rows: int, page: int, width: int,
                window: Optional[int]) -> int:
    """The most pages one sequence's ``rows`` consecutive positions in a
    resident block can see: the table's ``width``, or what their windows
    reach."""
    return width if window is None else window_pages(window, rows, page,
                                                     width)


def visit_extent(short_blocks: int, tall_blocks: int, tall_rows: int,
                 page: int, width: int, window: Optional[int]) -> int:
    """The static length of a walk's arrays: the most visits its blocks
    can have, a short block one sequence's, a tall block those of up to
    ``tall_rows / BLOCK_ROWS`` sequences."""
    return (short_blocks * _most_pages(BLOCK_ROWS, page, width, window) +
            tall_blocks * (tall_rows // BLOCK_ROWS) *
            _most_pages(tall_rows, page, width, window))


def _runs(xp, row_seq, qpos, kv_lens, *, decode_rows: int, tall_rows: int,
          page: int, width: int, window: Optional[int]):
    """The runs of a walk (:func:`visit_schedule` has the rule), one for
    each ``BLOCK_ROWS`` rows: ``(seq, first, n, lone, block)``, the run's
    sequence, its first page, how many pages it visits (0 for rows that
    ride in the run their block's leading rows make), whether it is the
    one step of a block that visits none, and the resident block it
    belongs to (static).  ``xp``: ``jnp`` on the device, ``numpy`` for the
    host's count of the same walk (:func:`visit_counts`)."""
    t = qpos.shape[0]
    nb, nd = t // BLOCK_ROWS, decode_rows // BLOCK_ROWS
    per_tall = tall_rows // BLOCK_ROWS
    nc = (nb - nd) // per_tall
    big = np.iinfo(np.int32).max
    qb = qpos.reshape(nb, BLOCK_ROWS)
    seq = row_seq.reshape(nb, BLOCK_ROWS)[:, 0]
    lo = xp.min(xp.where(qb >= 0, qb, big), axis=1)
    hi = xp.max(qb, axis=1)
    leads = np.ones((nd,), bool)
    at = np.arange(per_tall)
    if nc:
        # inside a tall block: the rows of one sequence are one run, led
        # by the first BLOCK_ROWS of them
        sq = seq[nd:].reshape(nc, per_tall)
        same = sq[:, :, None] == sq[:, None, :]
        lo = xp.concatenate([lo[:nd], xp.min(xp.where(
            same, lo[nd:].reshape(nc, 1, per_tall), big), axis=2).ravel()])
        hi = xp.concatenate([hi[:nd], xp.max(xp.where(
            same, hi[nd:].reshape(nc, 1, per_tall), -1), axis=2).ravel()])
        leads = xp.concatenate([leads, ~xp.any(
            same & (at[None, :] < at[:, None]), axis=2).ravel()])
    # (a table of a few entries is looked up by comparison: a gather out
    # of it costs the TPU more than the whole walk's arithmetic)
    slot = np.arange(kv_lens.shape[0])
    hi = xp.minimum(hi, xp.sum(xp.where(
        seq[:, None] == slot, kv_lens[None, :], 0), axis=1) - 1)
    first = xp.zeros((nb,), np.int32) if window is None \
        else xp.maximum(lo - window + 1, 0) // page
    most = np.concatenate([
        np.full(nd, _most_pages(BLOCK_ROWS, page, width, window)),
        np.full(nb - nd, _most_pages(tall_rows, page, width, window))]
    ).astype(np.int32)
    n = xp.where(leads & (hi >= 0) & (lo <= hi),
                 xp.minimum(hi // page - first + 1, most), 0)
    first = xp.where(n > 0, first, 0)
    # the one visit of a block that has none
    lone = n[:nd] == 0
    if nc:
        idle = xp.sum(n[nd:].reshape(nc, per_tall), axis=1) == 0
        lone = xp.concatenate([lone, (idle[:, None] & (at == 0)).ravel()])
    block = np.concatenate([np.arange(nd), nd + np.arange(nb - nd)
                            // per_tall]).astype(np.int32)
    return seq, first, n, lone, block


def visit_schedule(row_seq, qpos, kv_lens, *, decode_rows: int,
                   tall_rows: int, page: int, width: int,
                   window: Optional[int] = None) -> Visits:
    """The walk of one call, made in XLA from the tick's own arrays (once
    for every head group and, the arrays being a tick's, for every layer
    of a kind).

    Rows ``[0, decode_rows)`` stand in SHORT resident blocks of
    :data:`BLOCK_ROWS` rows (a decoding slot's), the rows behind them in
    TALL blocks of ``tall_rows`` (both multiples of ``BLOCK_ROWS``; every
    aligned ``BLOCK_ROWS`` rows belong to one sequence).  A RUN is what
    one sequence has in one resident block: a short block is one run, a
    tall block has one a sequence with rows in it.  A run visits the
    pages its live rows can see and no other: from page 0 (under a
    ``window``: from the first page its lowest row's window reaches) to
    the page of its highest row, the causal bound of THAT run, never past
    the sequence's length.  Runs follow each other by block; a block with
    no visit of its own gets one that computes nothing."""
    nb, nd = qpos.shape[0] // BLOCK_ROWS, decode_rows // BLOCK_ROWS
    seq, first, n, lone, block = _runs(
        jnp, row_seq, qpos, kv_lens, decode_rows=decode_rows,
        tall_rows=tall_rows, page=page, width=width, window=window)
    steps = n + lone
    # where each run's steps end (a running sum, as a triangle of sums:
    # one pass), and what a visit takes from its run, found by comparison
    # and not by a gather: runs of no step are passed over
    run = np.arange(nb)
    end = jnp.sum(jnp.where(run[None, :] <= run[:, None], steps[None, :], 0),
                  axis=1)
    start, count = end - steps, end[-1].astype(jnp.int32)
    extent = visit_extent(nd, (nb - nd) * BLOCK_ROWS // tall_rows, tall_rows,
                          page, width, window)
    tt = jnp.arange(extent, dtype=jnp.int32)
    mine = (start[None, :] <= tt[:, None]) & (tt[:, None] < end[None, :])
    seq, page_at, res, computes = jnp.sum(jnp.where(
        mine[:, :, None], jnp.stack(
            [seq, first - start, jnp.asarray(block), n > 0], axis=1)[None],
        0), axis=1).T
    edge = jnp.ones((1,), bool)
    turns = res[1:] != res[:-1]
    flags = (_FIRST * jnp.concatenate([edge, turns]) +
             _LAST * (jnp.concatenate([turns, edge]) | (tt == count - 1)) +
             _COMPUTE * computes + _TALL * (res >= nd))
    return Visits(flags, res, page_at + tt, seq, count)


def _tall_padded(xp, row_seq, qpos, decode_rows: int, tall: int):
    """``row_seq`` and ``qpos`` with the rows behind the short blocks
    padded to whole tall blocks (rows that see nothing)."""
    pad = -(qpos.shape[0] - decode_rows) % tall
    if not pad:
        return row_seq, qpos
    return (xp.concatenate([row_seq, xp.zeros((pad,), np.int32)]),
            xp.concatenate([qpos, xp.full((pad,), -1, np.int32)]))


def visit_counts(row_seq, qpos, kv_lens, *, decode_rows: int, tall_rows: int,
                 page: int, width: int, window: Optional[int] = None):
    """The host's count of the walk :func:`visit_schedule` makes of the
    same (numpy) arrays, for one KV-head group of one layer: ``(visits,
    computing, pages)``: the grid steps, those of them that fetch a page
    and compute, and the distinct (sequence, page) pairs among them, which
    is what a walk that read every page once would fetch.  The rows behind
    ``decode_rows`` are padded to whole tall blocks as the call pads
    them."""
    row_seq, qpos = _tall_padded(np, row_seq, qpos, decode_rows, tall_rows)
    seq, first, n, lone, _ = _runs(
        np, row_seq, qpos, kv_lens, decode_rows=decode_rows,
        tall_rows=tall_rows, page=page, width=width, window=window)
    # a sequence's pages: the union of its runs' [first, first + n)
    edges = np.zeros((len(kv_lens), int((first + n).max()) + 1), np.int32)
    np.add.at(edges, (seq, first), n > 0)
    np.subtract.at(edges, (seq, first + n), n > 0)
    pages = int((np.cumsum(edges, axis=1) > 0).sum())
    return int((n + lone).sum()), int(n.sum()), pages


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _ragged_kernel(flag_ref, block_ref, at_ref, seq_ref, pt_ref, layer_ref,
                   *rest, page_size: int, hb: int,
                   sm_scale: float, quantized: bool, shorts: bool,
                   talls: bool, window: Optional[int] = None):
    # grid (kv_head_groups, visits), ``hb`` KV heads a group: a step is one
    # visit of the walk (``Visits``, scalar-prefetched with the page table
    # and the layer: the body reads the flags, the page's place in its
    # sequence and the sequence; the index maps the rest).  A resident block's (m, l, acc)
    # persist in VMEM scratch from its first visit to its last.
    # Operands, each kind only where the call has blocks of it: for the
    # SHORT blocks qpos (1, RS, 1), the score rows' absolute positions,
    # group-expanded, as a sublane column so that the mask broadcasts
    # over the (rows, page) scores without a layout change, and q
    # (hb, 1, RS, D), RS = BLOCK_ROWS * group; for the TALL blocks qpos
    # and the rows' sequences (1, RT, 1) and q (hb, 1, RT, D), RT =
    # tall_rows * group; then k/v (1, page, hb * D), the group's lane slab
    # of one page, contiguous in the pool, head h at lanes [h * D,
    # (h + 1) * D); quantized adds ks/vs (1, page, KVH) scale blocks (all
    # KV heads: a (page, 1) block is not a legal TPU tile); one output a
    # kind, as its q.  Scratch: m/l (hb, R, LANES), acc (hb, R, D) at the
    # taller kind's rows; a short block uses the first RS.
    args = list(rest)
    short = [args.pop(0) for _ in range(2)] if shorts else None
    tall = [args.pop(0) for _ in range(3)] if talls else None
    k_ref, v_ref = args.pop(0), args.pop(0)
    ks_ref, vs_ref = (args.pop(0), args.pop(0)) if quantized else (None,) * 2
    if shorts:
        short.append(args.pop(0))
    if talls:
        tall.append(args.pop(0))
    m_scr, l_scr, acc_scr = args
    hg = pl.program_id(0)
    step = pl.program_id(1)
    flags = flag_ref[step]
    d = acc_scr.shape[2]

    def visit(rows, qpos_of, q_ref, o_ref, shared: bool = False):
        # one visit of a resident block of ``rows`` score rows, whose
        # positions ``qpos_of()`` gives ((rows, 1); -1: the row sees
        # nothing).  ``shared`` (tall blocks): a row the page is not for
        # keeps what it has
        @pl.when((flags & _FIRST) != 0)
        def _init():
            m_scr[:, :rows] = jnp.full((hb, rows, _LANES), -jnp.inf,
                                       jnp.float32)
            l_scr[:, :rows] = jnp.zeros((hb, rows, _LANES), jnp.float32)
            acc_scr[:, :rows] = jnp.zeros((hb, rows, d), jnp.float32)

        @pl.when((flags & _COMPUTE) != 0)
        def _compute():
            qpos = qpos_of()
            tok = at_ref[step] * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            # ONE inequality is the whole mask: causal for prefill rows,
            # length for decode rows, everything for padded rows (qpos −1)
            seen = tok <= qpos
            if window is not None:
                seen = seen & (tok > qpos - window)
            if shared:
                own = jnp.broadcast_to(qpos, (rows, d)) >= 0
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
                q = q_ref[h, 0]                        # (rows, D)
                kb = k_ref[0, :, h * d:(h + 1) * d]    # (page, D)
                vb = v_ref[0, :, h * d:(h + 1) * d]
                if quantized:
                    kb = kb.astype(jnp.float32) * head_scale(ksc,
                                                             hg * hb + h)
                    vb = vb.astype(jnp.float32) * head_scale(vsc,
                                                             hg * hb + h)
                s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                s = s * sm_scale                       # (rows, page)
                s = jnp.where(seen, s, DEFAULT_MASK_VALUE)

                m_prev = jnp.max(m_scr[h, :rows], axis=1, keepdims=True)
                l_prev = jnp.max(l_scr[h, :rows], axis=1, keepdims=True)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
                acc = acc_scr[h, :rows] * alpha + jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if shared:
                    # (another sequence's page leaves no trace in a row,
                    # not even a NaN times zero)
                    acc = jnp.where(own, acc, acc_scr[h, :rows])
                acc_scr[h, :rows] = acc
                m_scr[h, :rows] = jnp.broadcast_to(m_new, (rows, _LANES))
                l_scr[h, :rows] = jnp.broadcast_to(l_new, (rows, _LANES))

        @pl.when((flags & _LAST) != 0)
        def _finalize():
            for h in range(hb):
                l = jnp.max(l_scr[h, :rows], axis=1, keepdims=True)
                l = jnp.where(l == 0.0, 1.0, l)  # no page seen: zeros, not NaN
                o_ref[h, 0] = (acc_scr[h, :rows] / l).astype(o_ref.dtype)

    if shorts:
        qpos_ref, q_ref, o_ref = short
        pl.when((flags & _TALL) == 0)(functools.partial(
            visit, q_ref.shape[2], lambda: qpos_ref[0], q_ref, o_ref))
    if talls:
        tpos_ref, tseq_ref, tq_ref, to_ref = tall
        # a tall block works on ONE sequence a visit: the rows of the
        # others stand as rows that see nothing
        pl.when((flags & _TALL) != 0)(functools.partial(
            visit, tq_ref.shape[2],
            lambda: jnp.where(tseq_ref[0] == seq_ref[step], tpos_ref[0], -1),
            tq_ref, to_ref, shared=True))


# What a call's resident blocks and K/V slabs may take of a core's VMEM
# (a TPU v5e's is 128 MiB; a Mosaic kernel is scoped to 16 MiB of it
# unless it asks), and a tall block's height: the score rows (rows x group)
# it aims at and the most rows it takes.  A visit's products grow with the
# block's rows and its fetch does not: from some 128 score rows a KV head
# up the products hide the fetch (PERF.md s6, PR 42: a visit of 384 score
# rows x 8 heads takes 4.0 us, a page's 1 MB 1.3 us), and up to 384-512
# the MXU and the step's fixed cost are still better used; beyond, a block
# only wastes more on the rows of the sequences a visit is not for.
_VMEM_LIMIT = 48 << 20
_TALL_SCORE_ROWS = 512
_TALL_ROWS_MOST = 128


def tall_block_rows(group: int, hb: int, head_dim: int) -> int:
    """``C``: the rows of a tall resident block, a power of two times
    :data:`BLOCK_ROWS` (chunks and buckets are, so blocks and chunks end
    together) and a pure function of shapes as :func:`heads_per_cell` is:
    ``_TALL_SCORE_ROWS / group`` score rows a KV head, at most
    ``_TALL_ROWS_MOST`` rows (128 rows at group 1 and 2, 64 at groups of
    6 and 8), fewer where q and the output (double-buffered), the three
    carries and the two row columns of ``hb`` heads would not fit beside
    the K/V slabs' budget and the body's scores (q counted at four bytes
    an element, whatever it has)."""
    room = _VMEM_LIMIT - _KV_VMEM_BUDGET - (8 << 20)
    per_row = group * (hb * (2 * 2 * head_dim * 4 +
                             (2 * _LANES + head_dim) * 4) +
                       2 * 2 * _LANES * 4)
    rows = min(_TALL_ROWS_MOST, _TALL_SCORE_ROWS // group, room // per_row)
    return max(BLOCK_ROWS, 1 << (int(rows).bit_length() - 1))


def _ragged_pallas(q, k_pool, v_pool, k_scale, v_scale, layer, page_table,
                   kv_lens, row_seq, qpos, sm_scale, interpret: bool,
                   window: Optional[int] = None, decode_rows: int = 0,
                   walk: Optional[Walk] = None):
    """Kernel-path entry, on the STORED pool (``[L, pages, page,
    KVH * D]``) and a layer index.  REQUIRES block-uniform packing: T a
    multiple of :data:`BLOCK_ROWS` and every aligned block of rows
    belonging to ONE sequence (callers pad each sequence's rows to the
    block size — decode slots to one block, chunks to whole blocks);
    rows that violate uniformity would silently attend over the wrong
    pages, so the engine owns the packing and tests pin it against the
    reference path.  The first ``decode_rows`` rows (static; a multiple
    of ``BLOCK_ROWS``) are walked in resident blocks of ``BLOCK_ROWS``
    (a decoding slot's few rows against its whole context: the page
    fetch sets the pace and the rows ride along); the rows behind them
    in TALL blocks of :func:`tall_block_rows` rows, each page of a
    sequence fetched once for all the rows the sequence has in the
    block (a prefill chunk's: the products set the pace).  A tall block
    may hold rows of several sequences and works on one a visit.  With
    a ``window`` the live rows one sequence has in a block lie within
    as many consecutive positions as the block has rows (as the engine
    packs them: a chunk's rows follow each other, a slot's verify rows
    too), which is what bounds the pages a visit list can hold.  ``walk``:
    the call's walk where the caller has made it (:func:`ragged_walk`)."""
    t, h, d = q.shape
    page, kvh = k_pool.shape[2], k_pool.shape[3] // d
    enforce_that(t % BLOCK_ROWS == 0 and decode_rows % BLOCK_ROWS == 0
                 and 0 <= decode_rows <= t,
                 f"ragged kernel rows ({t}, of them {decode_rows} in short "
                 f"blocks) must pack to BLOCK_ROWS ({BLOCK_ROWS})",
                 context="serving")
    enforce_that(h % kvh == 0, f"num_heads ({h}) must be a multiple of "
                 f"num_kv_heads ({kvh})", context="serving")
    hb, tall = _cell_shape(t - decode_rows, h, kvh, d, page,
                           k_pool.dtype.itemsize, k_scale is not None)
    if walk is None:
        walk = _walk(kv_lens, row_seq, qpos, tall=tall, group=h // kvh,
                     page=page, width=page_table.shape[1],
                     decode_rows=decode_rows, window=window)
    # the layer is an OPERAND, never a static argument: one trace and
    # one lowering of the kernel then serve every layer of a step
    return _ragged_call(q, k_pool, v_pool, k_scale, v_scale,
                        jnp.asarray(layer, jnp.int32).reshape(1),
                        page_table.astype(jnp.int32), walk, hb=hb,
                        sm_scale=sm_scale, interpret=interpret, window=window,
                        decode_rows=decode_rows, tall=tall)


def tall_rows_for(region: int, group: int, hb: int, head_dim: int) -> int:
    """The rows of a call's tall blocks: :func:`tall_block_rows`, and never
    more than the ``region`` behind the short blocks holds (a call with no
    such rows has no tall block, whatever this says)."""
    return max(BLOCK_ROWS, min(tall_block_rows(group, hb, head_dim), region))


def _cell_shape(tall_region: int, num_heads: int, num_kv_heads: int,
                head_dim: int, page: int, kv_itemsize: int, quantized: bool):
    """``(hb, tall)``: the KV heads a grid cell takes and the rows of a
    tall block, for a call whose rows behind the short blocks are
    ``tall_region``."""
    hb = heads_per_cell(num_kv_heads, page, head_dim, kv_itemsize, quantized)
    return hb, tall_rows_for(tall_region, num_heads // num_kv_heads, hb,
                             head_dim)


@functools.partial(jax.jit, static_argnames=("tall", "group", "page", "width",
                                             "decode_rows", "window"))
def _walk(kv_lens, row_seq, qpos, *, tall: int, group: int, page: int,
          width: int, decode_rows: int, window: Optional[int]) -> Walk:
    row_seq, qpos = _tall_padded(jnp, row_seq.astype(jnp.int32),
                                 qpos.astype(jnp.int32), decode_rows, tall)
    visits = visit_schedule(row_seq, qpos, kv_lens.astype(jnp.int32),
                            decode_rows=decode_rows, tall_rows=tall,
                            page=page, width=width, window=window)

    def columns(x, rows):
        return jnp.repeat(x.reshape(-1, rows), group, axis=1)[..., None]

    return Walk(visits, columns(qpos[:decode_rows], BLOCK_ROWS),
                columns(qpos[decode_rows:], tall),
                columns(row_seq[decode_rows:], tall))


def ragged_walk(page_table, kv_lens, row_seq, qpos, *, num_heads: int,
                num_kv_heads: int, head_dim: int, page_size: int,
                kv_itemsize: int, quantized: bool = False,
                decode_rows: int = 0,
                window: Optional[int] = None) -> Walk:
    """The walk a kernel call at these shapes makes of these rows
    (:func:`visit_schedule`, and the rows' columns), for a caller with
    several calls over the same rows: a step's layers of one kind share
    the tick's arrays, so the engine makes the walk once and hands it to
    each call (``ragged_paged_attention(walk=)``).  The head counts are
    ONE chip's (under tensor parallelism a shard's: every shard walks the
    same)."""
    _, tall = _cell_shape(qpos.shape[0] - decode_rows, num_heads,
                          num_kv_heads, head_dim, page_size, kv_itemsize,
                          quantized)
    return _walk(kv_lens, row_seq, qpos, tall=tall,
                 group=num_heads // num_kv_heads, page=page_size,
                 width=page_table.shape[1], decode_rows=decode_rows,
                 window=window)


def _score_rows(x, blocks: int, rows: int, kvh: int, g: int):
    """``[blocks * rows, kvh * g, ...]`` -> ``[kvh, blocks, rows * g,
    ...]``: a block's rows with the ``g`` query heads of a KV head next to
    each other, so that one K/V page load feeds the whole head group."""
    x = x.reshape(blocks, rows, kvh, g, *x.shape[2:])
    x = jnp.moveaxis(x, 2, 0)
    return x.reshape(kvh, blocks, rows * g, *x.shape[4:])


def _rows_back(o, blocks: int, rows: int, kvh: int, g: int):
    """The inverse of :func:`_score_rows`: ``[blocks * rows, kvh * g, D]``."""
    o = o.reshape(kvh, blocks, rows, g, o.shape[-1])
    return jnp.moveaxis(o, 0, 2).reshape(blocks * rows, kvh * g, o.shape[-1])


# jitted on its own so that a step program of L layers traces and lowers
# the kernel once and calls it L times: the body is unrolled over the
# cell's heads, and Pallas lowers in Python in every process, persistent
# compile cache or not — per layer that is seconds of set-up
@functools.partial(jax.jit, static_argnames=(
    "hb", "sm_scale", "interpret", "window", "decode_rows", "tall"))
def _ragged_call(q, k_pool, v_pool, k_scale, v_scale, layer, page_table,
                 walk: Walk, *, hb: int, sm_scale: float, interpret: bool,
                 window: Optional[int] = None, decode_rows: int = 0,
                 tall: int = BLOCK_ROWS):
    t, h, d = q.shape
    _, pages, page, lanes = k_pool.shape
    kvh = lanes // d
    g = h // kvh
    quantized = k_scale is not None
    nd, nc = walk.short_pos.shape[0], walk.tall_pos.shape[0]
    # the rows behind the short blocks, to whole tall blocks
    pad = decode_rows + nc * tall - t
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad, h, d), q.dtype)])
    pm = page_table.shape[1]
    prefetch = [*walk.visits[:4], page_table, layer]

    # the pool [L, P, page, KVH*D] addressed as [L*P, page, KVH*D]: the
    # two tiled dims stay as they are, so this is no copy on a TPU, and
    # page p of the layer is row ``layer * P + p``.  The KV heads of
    # group hg are lanes [hg*hb*D, (hg+1)*hb*D) of that page's
    # (page, KVH*D) slab, so the index map addresses them as
    # (row, 0, hg) with a legal (page, hb*D) tile and no transpose.
    kt = k_pool.reshape(-1, page, lanes)
    vt = v_pool.reshape(-1, page, lanes)

    # TPU block shapes must end in (8k, 128k) or the array's own last
    # two dims — every spec below is written to that rule.  An index
    # map's arguments: head group, step, then the prefetched refs

    def short_at(s, block):
        # (while the tall blocks are worked on: the last short one, still)
        return jnp.minimum(block[s], nd - 1)

    def tall_at(s, block):
        # (while the short blocks are worked on: the first tall one)
        return jnp.maximum(block[s] - nd, 0)

    def short_col(hg, s, flags, block, *refs):
        return (short_at(s, block), 0, 0)

    def short_blk(hg, s, flags, block, *refs):
        return (hg, short_at(s, block), 0, 0)

    def tall_col(hg, s, flags, block, *refs):
        return (tall_at(s, block), 0, 0)

    def tall_blk(hg, s, flags, block, *refs):
        return (hg, tall_at(s, block), 0, 0)

    def pool_row(s, flags, block, at, seq, table, layer_):
        # the table is a ring under a window: page ``a`` at ``a mod width``
        return layer_[0] * pages + table[seq[s], at[s] % pm]

    def kv_idx(hg, s, *refs):
        return (pool_row(s, *refs), 0, hg)

    def scale_idx(hg, s, *refs):
        return (pool_row(s, *refs), 0, 0)

    in_specs, args, out_specs, out_shape = [], [], [], []
    rows = 0
    for blocks, height, col, blk, columns, at in (
            (nd, BLOCK_ROWS, short_col, short_blk, [walk.short_pos],
             slice(0, decode_rows)),
            (nc, tall, tall_col, tall_blk, [walk.tall_pos, walk.tall_seq],
             slice(decode_rows, None))):
        if not blocks:
            continue
        rows = max(rows, height * g)
        in_specs += [pl.BlockSpec((1, height * g, 1), col)] * len(columns)
        args += columns
        in_specs.append(pl.BlockSpec((hb, 1, height * g, d), blk))
        args.append(_score_rows(q[at], blocks, height, kvh, g))
        out_specs.append(pl.BlockSpec((hb, 1, height * g, d), blk))
        out_shape.append(jax.ShapeDtypeStruct(
            (kvh, blocks, height * g, d), q.dtype))
    in_specs += [pl.BlockSpec((1, page, hb * d), kv_idx)] * 2
    args += [kt, vt]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, kvh), scale_idx)] * 2
        args += [k_scale.reshape(-1, page, kvh),
                 v_scale.reshape(-1, page, kvh)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(kvh // hb, walk.visits.count),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),
            pltpu.VMEM((hb, rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, page_size=page, hb=hb,
                               sm_scale=sm_scale, quantized=quantized,
                               shorts=nd > 0, talls=nc > 0, window=window)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*prefetch, *args)
    back = [_rows_back(o, blocks, height, kvh, g)
            for o, (blocks, height) in zip(
                outs, [bh for bh in ((nd, BLOCK_ROWS), (nc, tall)) if bh[0]])]
    return jnp.concatenate(back)[:t] if len(back) > 1 else back[0][:t]


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
                           window: Optional[int] = None,
                           decode_rows: int = 0,
                           walk: Optional[Walk] = None):
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
    doc).

    ``decode_rows`` (static; the kernel's): how many leading rows are
    decoding slots' and stand in short resident blocks; the rows behind
    them, a prefill bucket's, are walked in tall blocks (0: all of them
    are; the result is the same, a decode row's visit then costs a tall
    block's products).  ``walk``: the call's schedule of visits where the
    caller has made it for several calls over the same rows
    (:func:`ragged_walk`, at these shapes and this ``decode_rows`` and
    ``window``)."""
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
                              window=None if window is None else int(window),
                              decode_rows=int(decode_rows), walk=walk)
    return _reference_on_layer(q, k_pool, v_pool, k_scale, v_scale, layer,
                               page_table, kv_lens, row_seq, qpos,
                               sm_scale=sm_scale, window=window)


def ragged_paged_attention_tp(mesh, axis, q, k_pool, v_pool, page_table,
                              kv_lens, row_seq, qpos, *, layer,
                              k_scale=None, v_scale=None,
                              sm_scale: Optional[float] = None,
                              use_kernel: Optional[bool] = None,
                              interpret: Optional[bool] = None,
                              decode_rows: int = 0,
                              walk: Optional[Walk] = None):
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

    if walk is not None:
        in_specs.append(jax.tree.map(lambda _: repl, walk))

    def local(qs, ks, vs, lyr, pt, ln, rs, qp, *rest):
        rest = list(rest)
        kss, vss = (rest.pop(0), rest.pop(0)) if k_scale is not None \
            else (None, None)
        return _ragged_pallas(qs, ks, vs, kss, vss, lyr, pt, ln, rs, qp,
                              float(sm_scale), bool(interpret),
                              decode_rows=int(decode_rows),
                              walk=rest[0] if rest else None)

    fn = shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=head, check_vma=False)
    args = [q, k_pool, v_pool, jnp.asarray(layer, jnp.int32),
            page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
            row_seq.astype(jnp.int32), qpos.astype(jnp.int32)]
    if k_scale is not None:
        args += [k_scale, v_scale]
    if walk is not None:
        args.append(walk)
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
