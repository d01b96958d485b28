"""Grouped matrix products over the rows an expert layer sorted by expert
(Pallas, TPU): ``out[r] = x[r] @ w[group of r]`` and its two gradients.

Layout (``tile_plan``): every group's rows start at a multiple of
``tile_m`` and a group takes at least one tile, so a row tile belongs to
exactly one group and the kernels need no masks inside a tile.  The rows
between a group's last real row and its tile's end are zeros of the
caller's making; the tiles after the last group are dead.  The plan is
made for the worst case (every (token, choice) pair on a held expert); the
buffer a call is handed may be any shorter length that holds the live
tiles (``parallel/moe.py dropless_rungs``), and only the live tiles are
computed: a dead grid step repeats the last live tile's block indices, so
it moves nothing and computes nothing, and the output rows of dead tiles
are never written (the caller never reads them).

Three kernels, as JAX's ``pallas.ops.tpu.megablox`` splits the work:
``moe_gmm`` (rows x group matrix, also with the matrix transposed, which is
the gradient of the rows) and ``moe_tgmm`` (rows^T x rows per group, the
gradient of the matrices).  Operands in their own dtype (bfloat16 under
the global policy), float32 accumulation.  The contraction is whole in
VMEM (2048 and 1536 here), so ``moe_gmm`` needs no accumulator, and a
group's matrix block is fetched once while consecutive row tiles of the
group stream under it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.kernel_util import interpret_default

TILE_M = 128


def _tile(n: int, prefs: Tuple[int, ...]) -> int:
    """The first preferred edge that divides ``n``, else ``n`` whole."""
    for edge in prefs:
        if n % edge == 0:
            return edge
    return n


def padded_rows(pairs: int, groups: int, tile_m: int = TILE_M) -> int:
    """Rows of the sorted buffer that holds up to ``pairs`` rows in
    ``groups`` tile-aligned groups, whatever their sizes."""
    return (pairs + tile_m - 1) // tile_m * tile_m + groups * tile_m


def tile_plan(counts: jax.Array, n_tiles: int, tile_m: int = TILE_M):
    """counts [G] rows of each group -> (offsets [G], the row each group
    starts at; tile_group [n_tiles], the group of each row tile;
    num_active [1], the tiles that hold a group)."""
    g = counts.shape[0]
    tiles = jnp.maximum(1, (counts + tile_m - 1) // tile_m)
    ends = jnp.cumsum(tiles)
    offsets = (ends - tiles) * tile_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles), side="right"), g - 1)
    return (offsets.astype(jnp.int32), tile_group.astype(jnp.int32),
            ends[-1:].astype(jnp.int32))


def _params(interpret: bool, sem: Tuple[str, ...]):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=sem)


# ---- rows x group matrix ----------------------------------------------------

def _gmm_kernel(tg_ref, na_ref, x_ref, w_ref, o_ref, *, transpose_rhs: bool):
    @pl.when(pl.program_id(1) < na_ref[0])
    def _live():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def gmm(x, w, tile_group, num_active, *, transpose_rhs: bool = False,
        tile_m: int = TILE_M, out_dtype=jnp.float32):
    """x [R, A]; w [G, A, B] (``transpose_rhs``: [G, B, A]) -> [R, B]."""
    r, a = x.shape
    b = w.shape[1] if transpose_rhs else w.shape[2]
    assert r % tile_m == 0 and w.shape[2 if transpose_rhs else 1] == a
    tn = _tile(b, (512, 256, 128))
    interpret = interpret_default()

    def row(n, m, tg, na):
        return jnp.minimum(m, na[0] - 1)

    w_block = (1, tn, a) if transpose_rhs else (1, a, tn)
    if transpose_rhs:
        w_idx = lambda n, m, tg, na: (tg[row(n, m, tg, na)], n, 0)  # noqa: E731
    else:
        w_idx = lambda n, m, tg, na: (tg[row(n, m, tg, na)], 0, n)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // tn, r // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, a),
                             lambda n, m, tg, na: (row(n, m, tg, na), 0)),
                pl.BlockSpec(w_block, w_idx),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tn), lambda n, m, tg, na: (row(n, m, tg, na), n)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, b), out_dtype),
        compiler_params=_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(tile_group, num_active, x, w)


# ---- rows^T x rows, per group -----------------------------------------------

def _tgmm_kernel(tg_ref, na_ref, x_ref, dy_ref, o_ref, acc, *, n_tiles: int):
    m = pl.program_id(2)
    na = na_ref[0]
    g = tg_ref[m]
    first = (m == 0) | (tg_ref[jnp.maximum(m - 1, 0)] != g)
    last = (m == na - 1) | (tg_ref[jnp.minimum(m + 1, n_tiles - 1)] != g)
    live = m < na

    @pl.when(live & first)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live)
    def _add():
        acc[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & last)
    def _store():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def tgmm(x, dy, tile_group, num_active, groups: int, *,
         tile_m: int = TILE_M, out_dtype=jnp.float32):
    """x [R, A], dy [R, B] -> [G, A, B]: ``x_g^T @ dy_g`` for each group's
    rows.  Every group has a tile, so every block is written."""
    r, a = x.shape
    b = dy.shape[1]
    assert r % tile_m == 0 and dy.shape[0] == r
    ta = _tile(a, (1024, 768, 512, 256, 128))
    tb = _tile(b, (512, 256, 128))
    n_tiles = r // tile_m
    interpret = interpret_default()

    def row(m, na):
        return jnp.minimum(m, na[0] - 1)

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a // ta, b // tb, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, ta),
                             lambda i, j, m, tg, na: (row(m, na), i)),
                pl.BlockSpec((tile_m, tb),
                             lambda i, j, m, tg, na: (row(m, na), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, ta, tb),
                lambda i, j, m, tg, na: (tg[row(m, na)], i, j)),
            scratch_shapes=[pltpu.VMEM((ta, tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, a, b), out_dtype),
        compiler_params=_params(interpret,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_tgmm",
    )(tile_group, num_active, x, dy)


# ---- the differentiable product ---------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, num_active, tile_m: int = TILE_M):
    """``x[r] @ w[group of r]`` over the tile-aligned layout: x [R, A] in
    the operands' dtype, w [G, A, B] in the parameters' (rounded to x's
    for the product) -> [R, B] float32.  The gradients go through the same
    kernels (``moe_gmm`` transposed, ``moe_tgmm``); the matrices' comes
    out in float32 and is not rounded on the way."""
    return gmm(x, w.astype(x.dtype), tile_group, num_active, tile_m=tile_m)


def _gm_fwd(x, w, tile_group, num_active, tile_m):
    wb = w.astype(x.dtype)
    return (gmm(x, wb, tile_group, num_active, tile_m=tile_m),
            (x, wb, jnp.zeros((), w.dtype), tile_group, num_active))


def _gm_bwd(tile_m, res, dy):
    x, wb, w_like, tile_group, num_active = res
    dy = dy.astype(x.dtype)
    dx = gmm(dy, wb, tile_group, num_active, transpose_rhs=True,
             tile_m=tile_m, out_dtype=x.dtype)
    dw = tgmm(x, dy, tile_group, num_active, wb.shape[0], tile_m=tile_m,
              out_dtype=w_like.dtype)
    return dx, dw, None, None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)
