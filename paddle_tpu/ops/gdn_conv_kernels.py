"""The delta-rule layer's prologue as two Pallas kernels (TPU).

``ops/gated_delta.py gated_delta_net`` projects a flat buffer to ``qkvz``
``[T, 2 Hk dk + 2 Hv dv]`` (columns ``[q | k | v | z]``).  What stands
between that product and the scan kernels (``ops/gdn_kernels.py``) is one
pass over the first ``C = 2 Hk dk + Hv dv`` columns: the causal depthwise
convolution inside each sequence, SiLU, and for the q and k heads the L2
normalisation (q scaled by ``dk ** -0.5``).  Left to XLA it was a padded
copy, a window and a select a tap, a reduction, a multiply, a column slice
and the copies into the scan's layout, each a pass over the buffer.

``qkv_conv_fwd``: the grid is (column block, row tile).  A cell reads a
``[rows, lanes]`` block of ``qkvz`` IN PLACE (the index map only ever names
blocks of the first ``C`` columns; a block is whole heads of one of q, k,
v) and the 8 rows above it (a second block of the same operand: the halo
of the ``K - 1`` rows a tap reaches back), and writes the block of q, k or
v, three outputs ``[T, Hk dk]``, ``[T, Hk dk]``, ``[T, Hv dv]``: the scan
kernels' operands.  An output that a cell does not write keeps its block
index (its first block before its columns come, its last one after), so
nothing of it moves until its own columns are done.

``qkv_conv_bwd``: the same grid, the row tiles the last and sequential
axis.  From the cotangents of q, k, v and ``qkvz`` it builds ``y``, the SiLU
and the norms again in VMEM for the tile and the 8 rows below it (the taps
reach FORWARD in the backward pass), writes ``dx`` and adds the tile's
share of ``dw`` into a block that stays resident over the row tiles.

Where a sequence starts is told by one int32 column ``[T, 1]``
(``tap_marks``): bit ``s - 1`` says that the row ``s`` back is of this
row's sequence (``causal_conv``'s rule: it carries this row's id; rows
before the buffer count as zero), bit ``7 + s`` the same of the row ``s``
ahead.  A row beyond the buffer (a last tile that is not whole, a halo past
the end) is never selected, whatever it holds.

Inside a cell the work goes ``CHUNK_ROWS`` rows (a loop) and a head's
lanes (traced once, unrolled where the kernel is lowered: ``_each_head``)
at a time, so that a chunk's ``y``, sigmoid and products stay in registers
and the heads' chains interleave.  A chunk reads its window ``[8 rows above
| chunk]`` of a VMEM scratch that holds ``[halo | tile]`` at an aligned
row; a tap's shifted rows are a static slice of that value.  Float32
throughout.  On the CPU the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.kernel_util import interpret_default

ROWS = 256          # rows a tile (a multiple of CHUNK_ROWS)
LANES = 1024        # most lanes a column block
CHUNK_ROWS = 64     # rows worked on at a time inside a cell
HALO = 8            # rows of a halo block: one sublane tile


class Dims(NamedTuple):
    """The layer's widths: key heads, value heads, their lanes."""
    hk: int
    hv: int
    dk: int
    dv: int

    @property
    def nq(self):
        return self.hk * self.dk

    @property
    def nv(self):
        return self.hv * self.dv

    @property
    def channels(self):
        return 2 * self.nq + self.nv


def lane_block(dims: Dims) -> Optional[int]:
    """Lanes of a column block: whole heads of one of q, k, v in whole
    128-lane tiles, the most under ``LANES``; ``None`` where the widths
    allow none (the caller then keeps the XLA composition)."""
    unit = math.lcm(dims.dk, dims.dv)
    if dims.dk % 128 or dims.dv % 128 or dims.nq % unit or dims.nv % unit:
        return None
    most = math.gcd(dims.nq, dims.nv) // unit
    return unit * max(m for m in range(1, most + 1)
                      if most % m == 0 and (unit * m <= LANES or m == 1))


def tap_marks(segment_ids: jax.Array, taps: int) -> jax.Array:
    """[T, 1] int32: bit ``s - 1`` set where row ``t - s`` is of row
    ``t``'s sequence, bit ``7 + s`` where row ``t + s`` is (``1 <= s <
    taps``); a row outside the buffer is of none."""
    assert taps - 1 <= HALO, taps
    seg = segment_ids.astype(jnp.int32)
    t = seg.shape[0]
    idx = jnp.arange(t, dtype=jnp.int32)
    marks = jnp.zeros((t,), jnp.int32)
    for s in range(1, taps):
        behind = (idx >= s) & (jnp.roll(seg, s) == seg)
        ahead = (idx < t - s) & (jnp.roll(seg, -s) == seg)
        marks = marks | (behind.astype(jnp.int32) << (s - 1)) \
            | (ahead.astype(jnp.int32) << (7 + s))
    return marks[:, None]


def _bit(marks, n: int):
    return (marks & (1 << n)) != 0


def _behind(marks, taps: int):
    """For each tap back, whether that row is of the row's sequence."""
    return [_bit(marks, s - 1) for s in range(1, taps)]


def _each_head(heads: int, body) -> None:
    """``body(h, None)`` for every head of the block: traced ONCE and
    unrolled where the kernel is lowered (the index is then a constant and
    the head's lanes a static slice), so that the heads' chains of one
    chunk interleave; tracing every head in Python took seconds of every
    run's set-up."""
    jax.lax.fori_loop(0, heads, body, None, unroll=True)


def _chunk_rows(rows: int) -> int:
    """Rows of a chunk: ``CHUNK_ROWS`` or, for a short tile, what divides
    it (a tile is whole sublane tiles)."""
    return math.gcd(rows, CHUNK_ROWS)


def _conv(win, w_ref, behind, lanes, taps: int):
    """``y`` of a chunk from its window ``[HALO + n, lanes]`` (the 8 rows
    above the chunk, then the chunk): the taps in ``causal_conv``'s order,
    the current row's first."""
    n = win.shape[0] - HALO
    y = win[HALO:] * w_ref[taps - 1:taps, lanes]
    for s in range(1, taps):
        y = y + jnp.where(behind[s - 1], win[HALO - s:HALO - s + n],
                          0.0) * w_ref[taps - 1 - s:taps - s, lanes]
    return y


def _part(j, lb: int, dims: Dims):
    """Which of q, k, v the column block ``j`` lies in (traced)."""
    nq = dims.nq // lb
    return j < nq, (j >= nq) & (j < 2 * nq), j >= 2 * nq


# ---- forward ------------------------------------------------------------------

def _fwd_kernel(x_ref, above_ref, w_ref, marks_ref, q_ref, k_ref, v_ref,
                x_scr, *, taps, dims, rows, lb):
    x_scr[0:HALO] = above_ref[...]
    x_scr[HALO:HALO + rows] = x_ref[...]

    n = _chunk_rows(rows)

    def part(out_ref, width, scale):
        """A block of whole heads of ``width`` lanes; ``scale`` is ``None``
        for v, which is not normalised."""
        def chunk(c, _):
            r = pl.multiple_of(c * n, n)
            behind = _behind(marks_ref[pl.ds(r, n)], taps)

            def head(h, _):
                lanes = pl.ds(pl.multiple_of(h * width, width), width)
                y = _conv(x_scr[pl.ds(r, HALO + n), lanes], w_ref, behind,
                          lanes, taps)
                a = y * jax.nn.sigmoid(y)
                if scale is not None:
                    a = a * jax.lax.rsqrt(
                        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                    if scale != 1.0:
                        a = a * scale
                out_ref[pl.ds(r, n), lanes] = a

            _each_head(lb // width, head)

        jax.lax.fori_loop(0, rows // n, chunk, None)

    is_q, is_k, is_v = _part(pl.program_id(0), lb, dims)
    pl.when(is_q)(lambda: part(q_ref, dims.dk, float(dims.dk) ** -0.5))
    pl.when(is_k)(lambda: part(k_ref, dims.dk, 1.0))
    pl.when(is_v)(lambda: part(v_ref, dims.dv, None))


# ---- backward -----------------------------------------------------------------

def _bwd_kernel(x_ref, above_ref, below_ref, w_ref, marks_ref, mbelow_ref,
                dq_ref, dk_ref, dv_ref, dqb_ref, dkb_ref, dvb_ref,
                dx_ref, dw_ref, x_scr, dy_scr, *, taps, dims, rows, lb, total):
    """``dy_scr`` holds the cotangent of ``y`` for the tile's rows and the
    8 below; ``dx_t = sum_s w[K - 1 - s] dy_{t + s}`` over the rows ahead
    that are of row ``t``'s sequence."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _first_tile():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x_scr[0:HALO] = above_ref[...]
    x_scr[HALO:HALO + rows] = x_ref[...]
    x_scr[HALO + rows:] = below_ref[...]

    n = _chunk_rows(rows)

    def part(cot_ref, cot_below_ref, width, scale):
        heads = lb // width
        at = lambda h: pl.ds(pl.multiple_of(h * width, width), width)  # noqa: E731,E501

        def dy_of(win, behind, da, lanes):
            """The cotangent of ``y`` for a chunk from its window."""
            y = _conv(win, w_ref, behind, lanes, taps)
            sig = jax.nn.sigmoid(y)
            if scale is not None:
                a = y * sig
                inv = jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                along = jnp.sum(a * da, axis=-1, keepdims=True)
                da = (da - a * (inv * inv * along)) * (inv * scale)
            return da * (sig * (1.0 + y * (1.0 - sig)))

        def tile_chunk(c, _):
            r = pl.multiple_of(c * n, n)
            behind = _behind(marks_ref[pl.ds(r, n)], taps)
            # the tile's own rows give dw; none beyond the buffer, whose
            # marks are no marks
            real = i * rows + r + jax.lax.broadcasted_iota(
                jnp.int32, (n, 1), 0) < total
            took = [real] + [real & b for b in behind]

            def head(h, _):
                lanes = at(h)
                win = x_scr[pl.ds(r, HALO + n), lanes]
                dy = dy_of(win, behind, cot_ref[pl.ds(r, n), lanes], lanes)
                dy_scr[pl.ds(r, n), lanes] = dy
                for s in range(taps):
                    dw_ref[taps - 1 - s:taps - s, lanes] += jnp.sum(
                        jnp.where(took[s], dy * win[HALO - s:HALO - s + n],
                                  0.0), axis=0, keepdims=True)

            _each_head(heads, head)

        jax.lax.fori_loop(0, rows // n, tile_chunk, None)

        def below(h, _):    # the 8 rows below: their dy reaches back
            lanes = at(h)
            dy_scr[rows:, lanes] = dy_of(
                x_scr[rows:, lanes], _behind(mbelow_ref[...], taps),
                cot_below_ref[:, lanes], lanes)

        _each_head(heads, below)

        def dx_chunk(c, _):
            r = pl.multiple_of(c * n, n)
            marks = marks_ref[pl.ds(r, n)]
            ahead = [_bit(marks, 7 + s) for s in range(1, taps)]

            def head(h, _):
                lanes = at(h)
                win = dy_scr[pl.ds(r, n + HALO), lanes]
                dx = win[:n] * w_ref[taps - 1:taps, lanes]
                for s in range(1, taps):
                    dx = dx + jnp.where(ahead[s - 1], win[s:s + n], 0.0) \
                        * w_ref[taps - 1 - s:taps - s, lanes]
                dx_ref[pl.ds(r, n), lanes] = dx

            _each_head(heads, head)

        jax.lax.fori_loop(0, rows // n, dx_chunk, None)

    is_q, is_k, is_v = _part(pl.program_id(0), lb, dims)
    pl.when(is_q)(lambda: part(dq_ref, dqb_ref, dims.dk,
                               float(dims.dk) ** -0.5))
    pl.when(is_k)(lambda: part(dk_ref, dkb_ref, dims.dk, 1.0))
    pl.when(is_v)(lambda: part(dv_ref, dvb_ref, dims.dv, None))


# ---- the calls ----------------------------------------------------------------

def _layout(t: int, dims: Dims, taps: int):
    """(grid, rows a tile, lanes a block, the operands' block specs).  The
    grid is (column block ``j``, row tile ``i``); a halo is the 8-row block
    above or below the tile, held inside the buffer (a halo outside it is
    never selected)."""
    lb = lane_block(dims)
    rows = min(ROWS, -(-t // HALO) * HALO)
    tiles, blocks, per = pl.cdiv(t, rows), dims.channels // lb, rows // HALO
    last8 = pl.cdiv(t, HALO) - 1
    above = lambda i: jnp.maximum(i * per - 1, 0)               # noqa: E731
    below = lambda i: jnp.minimum((i + 1) * per, last8)         # noqa: E731

    def own(first, count, halo=False):
        """One of q, k, v (or its cotangent): its own column blocks while
        the grid is in them, its first block before and its last one after:
        a block that stands still is neither fetched nor written back."""
        def at(j, i):
            row = jnp.where(j < first, 0,
                            jnp.where(j >= first + count, tiles - 1, i))
            return (below(row) if halo else row,
                    jnp.clip(j - first, 0, count - 1))
        return pl.BlockSpec((HALO if halo else rows, lb), at)

    nq = dims.nq // lb
    parts = [(0, nq), (nq, nq), (2 * nq, blocks - 2 * nq)]
    return (blocks, tiles), rows, lb, {
        "x": pl.BlockSpec((rows, lb), lambda j, i: (i, j)),
        "above": pl.BlockSpec((HALO, lb), lambda j, i: (above(i), j)),
        "below": pl.BlockSpec((HALO, lb), lambda j, i: (below(i), j)),
        "w": pl.BlockSpec((taps, lb), lambda j, i: (0, j)),
        "marks": pl.BlockSpec((rows, 1), lambda j, i: (i, 0)),
        "marks_below": pl.BlockSpec((HALO, 1), lambda j, i: (below(i), 0)),
        "own": [own(*p) for p in parts],
        "own_below": [own(*p, halo=True) for p in parts],
    }


def _params(interpret):
    # no axis is split over cores: an output that stands still relies on
    # the cells coming in the grid's order
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


# each call site is jitted on its own, as the scan's (``gdn_kernels``)
@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _conv_fwd(qkvz, wt, marks, *, dims, interpret):
    t, taps = qkvz.shape[0], wt.shape[0]
    grid, rows, lb, sp = _layout(t, dims, taps)
    out = lambda n: jax.ShapeDtypeStruct((t, n), jnp.float32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, dims=dims, rows=rows,
                          lb=lb),
        grid=grid,
        in_specs=[sp["x"], sp["above"], sp["w"], sp["marks"]],
        out_specs=sp["own"],
        out_shape=[out(dims.nq), out(dims.nq), out(dims.nv)],
        scratch_shapes=[pltpu.VMEM((HALO + rows, lb), jnp.float32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="qkv_conv_fwd")(qkvz, qkvz, wt, marks)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _conv_bwd(qkvz, wt, marks, dq, dk, dv, *, dims, interpret):
    """(dx [T, C], dw [K, C])."""
    t, taps = qkvz.shape[0], wt.shape[0]
    grid, rows, lb, sp = _layout(t, dims, taps)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, dims=dims, rows=rows,
                          lb=lb, total=t),
        grid=grid,
        in_specs=[sp["x"], sp["above"], sp["below"], sp["w"], sp["marks"],
                  sp["marks_below"], *sp["own"], *sp["own_below"]],
        out_specs=[sp["x"], sp["w"]],
        out_shape=[jax.ShapeDtypeStruct((t, dims.channels), jnp.float32),
                   jax.ShapeDtypeStruct(wt.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((2 * HALO + rows, lb), jnp.float32),
                        pltpu.VMEM((HALO + rows, lb), jnp.float32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="qkv_conv_bwd")(qkvz, qkvz, qkvz, wt, marks, marks,
                             dq, dk, dv, dq, dk, dv)


# ---- the differentiable function ----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qkv_conv(qkvz, wt, marks, dims):
    return tuple(_conv_fwd(qkvz, wt, marks, dims=dims,
                           interpret=interpret_default()))


def _qkv_conv_vjp_fwd(qkvz, wt, marks, dims):
    # the operands are all the backward kernel needs: nothing of [T, C]
    # is kept beside the projection itself
    return _qkv_conv(qkvz, wt, marks, dims), (qkvz, wt, marks)


def _qkv_conv_vjp_bwd(dims, res, cots):
    qkvz, wt, marks = res
    dx, dwt = _conv_bwd(qkvz, wt, marks, *cots, dims=dims,
                        interpret=interpret_default())
    # z's columns took no part
    return (jnp.pad(dx, ((0, 0), (0, qkvz.shape[1] - dx.shape[1]))), dwt,
            None)


_qkv_conv.defvjp(_qkv_conv_vjp_fwd, _qkv_conv_vjp_bwd)


def qkv_conv(qkvz: jax.Array, w: jax.Array, segment_ids: jax.Array,
             dims: Dims):
    """From the projection's columns to the recurrence's operands:
    ``silu(causal_conv(qkvz[:, :C], w, segment_ids))`` cut into q, k, v, q
    and k L2-normalised a head (``1e-6`` inside the root) and q scaled by
    ``dk ** -0.5``.  qkvz: [T, C + Hv dv] float32 (the ``z`` columns are
    not read); w: [C, K]; segment_ids: [T].  Returns q [T, Hk dk], k [T, Hk
    dk], v [T, Hv dv], float32.  ``lane_block(dims)`` must not be
    ``None``."""
    assert lane_block(dims) is not None, dims
    return _qkv_conv(qkvz.astype(jnp.float32), w.astype(jnp.float32).T,
                     tap_marks(segment_ids, w.shape[1]), dims)
