"""The selective state-space recurrence (Mamba-2, "SSD") with a state
handed in and out, in the two forms a served model needs.

Per head ``h`` a state ``H`` in ``R^{P x N}`` (``P`` lanes of the head,
``N`` the state size) and, token by token::

    H <- exp(dt_t A_h) H + dt_t * outer(xs_t[h], B_t[g])
    y_t[h] = H C_t[g] + D_h xs_t[h]            g = h // (heads / groups)

``A_h < 0``, ``dt_t >= 0``; ``B`` and ``C`` are shared by the heads of a
group.  That recurrence is the definition (``benchmarks/references/
falcon_h1.py`` and the tests run it).  A row with ``dt = 0`` and ``xs = 0``
is the identity on the state (``exp(0) H + 0``, bit for bit): that is how
a caller passes padding and invalid rows through either form.

:func:`ssd_step` advances ``[S]`` states by one token each: elementwise on
the state, a pass that reads and writes it once.

:func:`ssd_chunks` runs a flat buffer ``[T, ...]`` of rows of several
sequences (a sequence's rows contiguous, a sequence may start at any row)
from ``state_in[segment]`` to every segment's final state.  A sequence's
rows are taken ``chunk`` at a time, counted from its own first row, so a
piece never holds two sequences and no decay crosses from one to the
next; inside a piece the decays' running sums ``G`` give the
lower-triangular ``exp(G_t - G_s) dt_s (C_t . B_s)`` that carries row
``s`` to row ``t``, the incoming state reaches row ``t`` as ``exp(G_t) H
C_t``, and the piece's last row writes ``exp(G_last) H + sum_s
exp(G_last - G_s) dt_s outer(xs_s, B_s)`` back to ``state[segment]``,
where the sequence's next piece (in this call or the next) reads it.  The
pieces are walked by one ``while_loop`` over the buffer, a step a piece:
its trip count is the pieces there are, not a bound on them.

Plain ``jax.numpy`` (no kernel), float32, every product at the highest
precision: the decays, the running sums and the carried state never pass
through a bfloat16 rounding.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = ["CHUNK", "ssd_step", "ssd_chunks"]

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def ssd_step(xs: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, state: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One token for each of ``[S]`` slots.  xs ``[S, H, P]``; dt ``[S,
    H]`` (after softplus); A, D ``[H]``; B, C ``[S, G, N]``; state ``[S, H,
    P, N]`` float32.  Returns ``(y [S, H, P], state)``."""
    s, h, p = xs.shape
    g, n = B.shape[1], B.shape[2]
    r = h // g
    with jax.named_scope("ssd_step"):
        xs, dt = xs.astype(jnp.float32), dt.astype(jnp.float32)
        decay = jnp.exp(dt * A.astype(jnp.float32))            # [S, H]
        old = state.reshape(s, g, r, p, n)
        new = decay.reshape(s, g, r, 1, 1) * old + \
            (dt[..., None] * xs).reshape(s, g, r, p, 1) * \
            B.astype(jnp.float32)[:, :, None, None, :]
        y = jnp.sum(new * C.astype(jnp.float32)[:, :, None, None, :],
                    axis=-1).reshape(s, h, p)
        return (y + D.astype(jnp.float32)[:, None] * xs,
                new.reshape(state.shape))


def _piece(xs, dt, A, B, C, D, h0):
    """One piece of one sequence: ``L`` rows (those behind the piece's
    last hold ``dt = 0`` and ``xs = 0``) from the state ``h0 [H, P, N]``.
    Returns ``(y [L, H, P], the state after the last row)``."""
    l, h, p = xs.shape
    g, n = B.shape[1], B.shape[2]
    r = h // g
    cum = jnp.cumsum(dt * A, axis=0)                           # G, [L, H]
    # row s reaches row t >= s through exp(G_t - G_s) <= 1
    t_i = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    reach = jnp.exp(jnp.where((s_i <= t_i)[:, :, None],
                              cum[:, None, :] - cum[None, :, :], -jnp.inf))
    cb = jnp.einsum("tgn,sgn->tsg", C, B, precision=_HI)       # [L, L, G]
    w = (reach * dt[None, :, :]).reshape(l, l, g, r) * cb[..., None]
    y = jnp.einsum("tsh,shp->thp", w.reshape(l, l, h), xs, precision=_HI)
    # the incoming state, decayed to each row
    hg = h0.reshape(g, r, p, n)
    y = y + jnp.exp(cum)[:, :, None] * jnp.einsum(
        "tgn,grpn->tgrp", C, hg, precision=_HI).reshape(l, h, p)
    # what the piece leaves: every row decayed to the last one
    tail = jnp.exp(cum[-1][None, :] - cum) * dt                # [L, H]
    h1 = jnp.exp(cum[-1]).reshape(g, r, 1, 1) * hg + jnp.einsum(
        "sgrp,sgn->grpn", (tail[:, :, None] * xs).reshape(l, g, r, p), B,
        precision=_HI)
    return y + D[:, None] * xs, h1.reshape(h0.shape)


def ssd_chunks(xs: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, segment_ids: jax.Array,
               state_in: jax.Array, chunk: int = CHUNK
               ) -> Tuple[jax.Array, jax.Array]:
    """A flat buffer of several sequences' rows.  xs ``[T, H, P]``; dt
    ``[T, H]`` (after softplus); A, D ``[H]``; B, C ``[T, G, N]``;
    ``segment_ids [T]`` int32: a row's sequence, which is its state's
    index in ``state_in [S, H, P, N]``; the rows of a sequence are
    contiguous, and a row with a negative id belongs to none (it is
    passed over: its ``y`` is zero; such rows behind the last sequence
    cost nothing).  A sequence starts from
    ``state_in[id]`` (zeros there for a sequence that begins).  Returns
    ``(y [T, H, P], state_out [S, H, P, N])``: ``state_out[id]`` is the
    state behind the sequence's last row, and a state whose id the buffer
    does not hold is returned as it came."""
    t, h, p = xs.shape
    c = int(chunk)
    with jax.named_scope("ssd_chunks"):
        f32 = jnp.float32
        A, D = A.astype(f32), D.astype(f32)
        # (a piece's window may reach c - 1 rows behind the buffer)
        rows = lambda a: jnp.pad(a.astype(f32), (  # noqa: E731
            (0, c),) + ((0, 0),) * (a.ndim - 1))
        xs, dt, B, C = rows(xs), rows(dt), rows(B), rows(C)
        seg = jnp.pad(segment_ids.astype(jnp.int32), (0, c),
                      constant_values=-1)
        # one past the last row of the run of equal ids a row lies in
        idx = jnp.arange(t + c, dtype=jnp.int32)
        last = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
        run_end = jax.lax.cummin(jnp.where(last, idx + 1, t + c),
                                 reverse=True)
        inside = jnp.arange(c, dtype=jnp.int32)

        def window(a, at):
            return jax.lax.dynamic_slice_in_dim(a, at, c, axis=0)

        def step(carry):
            at, state, y = carry
            # (no branch: a run of rows of no sequence is a piece without
            # a live row, the identity on the state it is given)
            sid, left = jnp.maximum(seg[at], 0), run_end[at] - at
            n = jnp.where(seg[at] >= 0, jnp.minimum(left, c), left)
            live = (inside < n) & (seg[at] >= 0)
            got, h1 = _piece(
                jnp.where(live[:, None, None], window(xs, at), 0.0),
                jnp.where(live[:, None], window(dt, at), 0.0), A,
                window(B, at), window(C, at), D, state[sid])
            y = jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(live[:, None, None], got, window(y, at)), at,
                axis=0)
            return at + n, state.at[sid].set(h1), y

        # behind the last row of any sequence nothing is walked
        end = jnp.max(jnp.where(seg >= 0, idx + 1, 0))
        _, state, y = jax.lax.while_loop(
            lambda carry: carry[0] < end, step,
            (jnp.zeros((), jnp.int32), state_in.astype(f32),
             jnp.zeros((t + c, h, p), f32)))
        return y[:t], state
