"""The chunked gated delta rule as two Pallas kernels (TPU).

``ops/gated_delta.py gated_delta_rule`` hands over a flat buffer cut into
``n`` chunks of ``c`` tokens.  A grid cell is one chunk of one KEY head;
the chunks of a head follow each other (the second grid axis, sequential)
and the state ``S`` of the head's value heads stays in VMEM scratch from
one chunk to the next.  So a chunk's ``c x c`` tiles (``K K^T``, ``Q K^T``,
the decays ``D``, ``A``, ``(I + A)^{-1}`` and their cotangents), what the
chain takes a chunk (``U``, ``W``, ``q a``, ``k`` decayed to the chunk's
end) and what it returns (the chunk's updates, the state's share of ``o``)
are made, used and dropped in VMEM; HBM sees q, k, v, the scalars, o, and
the state each chunk starts from (``[n, Hv, dk, dv]``, which the backward
kernel reads).

``gdn_chunk_fwd``: per chunk ``T = (I + A)^{-1}``, ``U = T (beta v)``, ``W
= T (beta a k)``; then per value head ``new = U - W S``, ``o = (q a) S + (Q
K^T * D) new``, ``S <- keep S + (k decayed)^T new``.

``gdn_chunk_bwd``: the chunks last to first, the cotangent of ``S`` in
scratch; it builds the same tiles again from q, k, v and the state the
chunk started from, and returns the cotangents of q, k, v, the running
sums and the betas (``dA = -T^T dT T^T``; ``dD`` folds into the running
sums as row sums minus column sums).

A cell serves its key head's ``rep = Hv / Hk`` value heads AT ONCE, as one
system of ``p = rep c`` rows (a head after the other) whose ``p x p`` tiles
are zero outside the heads' diagonal blocks, so ``K K^T`` and ``Q K^T`` are
formed once a key head, and with ``c`` 64 and two heads the products fill
the MXU's 128 rows and columns, where two chains of 64 x 64 products each
waited out the other's latency (on the chip the inverse took 6.1 ms a call
as two chains and 4.1 as one; ``rep`` beyond ``128 / c`` pays for the zero
blocks).  q, k, v and o are read and written where the layer keeps them
(``[T, H * d]``, a ``(c, d)`` block at column block ``h``).  The per-token
scalars come as ROWS (``[.., r, p]``, tokens along the lanes): an array
that ends in ``[c, 1]`` would be padded 128-fold in HBM; a kernel turns a
row into a column with the identity's mask and a reduction.

Precision as the plain chunked form had it: the products take operands of
the policy's dtype ``ct`` (bfloat16 under ``FLAGS.use_bf16``) and
accumulate in float32; the running sums, ``D``, ``A``, the inverse, the two
products that differentiate it and the carried ``S`` are float32, the
inverse's products at the highest precision.  The inverse is block forward
substitution (``_unit_lower_inverse``), never a sum of powers.  On the CPU
the kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.kernel_util import interpret_default
from paddle_tpu.topology import keep

NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
HIGHEST = jax.lax.Precision.HIGHEST


def _dot(x, y, dims=NN, precision=None):
    """``x @ y`` (``NT``: ``x @ y^T``, ``TN``: ``x^T @ y``), float32 out."""
    return jax.lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _ij(p: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0),
            jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))


def _col(row):
    """[1, p] -> [p, 1]."""
    i, j = _ij(row.shape[1])
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def _row(col):
    """[p, 1] -> [1, p]."""
    i, j = _ij(col.shape[0])
    return jnp.sum(jnp.where(i == j, col, 0.0), axis=0, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _colsum(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _stack(ref, rep: int, d: int):
    """A [c, rep * d] block (heads side by side) as [rep * c, d] float32
    (heads one under the other)."""
    return jnp.concatenate([ref[:, r * d:(r + 1) * d] for r in range(rep)],
                           axis=0).astype(jnp.float32)


def _repeat(ref, rep: int):
    """A key head's [c, d] block under itself for each value head."""
    x = ref[...].astype(jnp.float32)
    return jnp.concatenate([x] * rep, axis=0) if rep > 1 else x


def _fold(x, rep: int):
    """[rep * c, d] -> [c, d]: the sum over the value heads."""
    c = x.shape[0] // rep
    return sum(x[r * c:(r + 1) * c] for r in range(rep))


def _unit_lower_inverse(a, c: int):
    """``(I + a)^{-1}`` for ``a`` [p, p] that is strictly lower-triangular
    inside its diagonal blocks of ``c`` (a power of two) and zero outside
    them, by block forward substitution: the inverse of a block-triangular
    ``[[M11, 0], [M21, M22]]`` is ``[[T11, 0], [-T22 M21 T11, T22]]``.
    ``t`` starts as the inverse of the 1 x 1 diagonal blocks (the identity)
    and each round joins neighbouring diagonal blocks of size ``b`` into
    blocks of ``2 b``: ``t <- t - t (a * below_b) t``, where ``below_b``
    keeps ``a``'s ``M21`` blocks; ``log2 c`` rounds.  The first needs no
    product (``t`` is the identity), the others two float32 products at
    the highest precision; only the rows of the ``M21`` blocks change, so
    once those are whole sublane tiles (``b >= 8``) only they, half the
    rows, go through the MXU.  Every intermediate is a block of the
    inverse of a leading part of ``I + a``, as benign as the result; the
    shorter ``(I - a)(I + a^2)(I + a^4)...`` sums powers that reach
    ``binomial(c, c / 2)`` when a chunk's keys align, and loses everything
    to cancellation (it made a run diverge on the chip)."""
    p = a.shape[0]
    assert c & (c - 1) == 0 and p % c == 0, (p, c)
    i, j = _ij(p)

    def below(s):                                       # b = 1 << s
        return ((i >> (s + 1)) == (j >> (s + 1))) \
            & (((i >> s) & 1) == 1) & (((j >> s) & 1) == 0)

    t = jnp.where(i == j, 1.0, 0.0) - jnp.where(below(0), a, 0.0)
    for s in range(1, c.bit_length() - 1):
        b, x = 1 << s, jnp.where(below(s), a, 0.0)
        if b % 8:
            t = t - _dot(_dot(t, x, precision=HIGHEST), t, precision=HIGHEST)
            continue
        blocks = [t[lo:lo + b] for lo in range(0, p, b)]
        low = jnp.concatenate(blocks[1::2], axis=0)     # the M21 blocks' rows
        low = low - _dot(_dot(low, x, precision=HIGHEST), t,
                         precision=HIGHEST)
        blocks[1::2] = [low[lo:lo + b] for lo in range(0, p // 2, b)]
        t = jnp.concatenate(blocks, axis=0)
    return t


def _pick(row, lane: int):
    """One entry of a [1, p] row, [1, 1]."""
    j = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return _rowsum(jnp.where(j == lane, row, 0.0))


def _chunk_tiles(q_ref, k_ref, v_ref, rows_ref, marks_ref, rep, c, dv, ct):
    """What the forward and the backward kernel both build for a chunk of
    a key head's ``rep`` value heads (``p = rep c`` rows): q, k (repeated)
    and v (stacked) in float32, ``kk = k k^T``, beta as a column, the
    strict decays ``D_ij = exp(G_i - G_j)`` for ``j < i`` in one segment
    and one head (zero elsewhere, and no overflow where it is zero), ``T =
    (I + A)^{-1}`` with ``A = beta * kk * D``, the two row weights (``a``,
    the decay from the chunk's start, for rows that read the incoming
    state; the decay to the chunk's end, for rows that write the outgoing
    one), what the chain takes (``U = T (beta v)`` float32, ``W = T (beta a
    k)``, ``q a``, ``k`` decayed to the end: in ``ct``), and each head's
    ``keep``: what is left of the incoming state at the chunk's end ([1,
    1]; zero where the last row's segment began inside the chunk)."""
    q, k, v = _repeat(q_ref, rep), _repeat(k_ref, rep), _stack(v_ref, rep, dv)
    kb = k.astype(ct)
    kk = _dot(kb, kb, NT)
    cum_row, lid = rows_ref[0:1, :], marks_ref[0:1, :]
    i, j = _ij(kk.shape[0])
    sh = c.bit_length() - 1
    lower = (_col(lid) == lid) & ((i >> sh) == (j >> sh)) & (i > j)
    cum, beta = _col(cum_row), _col(rows_ref[1:2, :])
    dm = jnp.where(lower, jnp.exp(jnp.where(lower, cum - cum_row, 0.0)), 0.0)
    inv = _unit_lower_inverse(beta * (kk * dm), c)
    a_in = jnp.exp(cum) * _col(marks_ref[1:2, :])
    ends = _rowsum(jnp.where(j == (i | (c - 1)), cum_row, 0.0))
    w_out = jnp.exp(ends - cum) * _col(marks_ref[2:3, :])
    tb = inv.astype(ct)
    u = _dot(tb, (beta * v).astype(ct))
    w = _dot(tb, ((beta * a_in) * k).astype(ct)).astype(ct)
    reads_last = _pick(marks_ref[1:2, :], c - 1)
    keep = [jnp.exp(_pick(cum_row, (r + 1) * c - 1)) * reads_last
            for r in range(rep)]
    return dict(q=q, k=k, v=v, kb=kb, kk=kk, beta=beta, dm=dm, inv=inv,
                tb=tb, a_in=a_in, w_out=w_out, u=u, w=w,
                qin=(q * a_in).astype(ct), kout=(k * w_out).astype(ct),
                keep=keep, eye=jnp.where(i == j, 1.0, 0.0))


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, rows_ref, marks_ref,
                      o_ref, s_ref, state, *, rep, c, dv, ct):
    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    t = _chunk_tiles(q_ref, k_ref, v_ref, rows_ref, marks_ref, rep, c, dv, ct)
    new, from_state = [], []
    for r in range(rep):
        sl = slice(r * c, (r + 1) * c)
        s = state[r]
        s_ref[r] = s                                     # for the backward
        both = _dot(jnp.concatenate([t["w"][sl], t["qin"][sl]], axis=0),
                    s.astype(ct))                        # [W; q a] S
        new.append((t["u"][sl] - both[:c]).astype(ct))   # the chunk's updates
        from_state.append(both[c:])
        state[r] = t["keep"][r] * s + _dot(t["kout"][sl], new[r], TN)
    qk = _dot(t["q"].astype(ct), t["kb"], NT)
    o = jnp.concatenate(from_state, axis=0) + _dot(
        (qk * (t["dm"] + t["eye"])).astype(ct), jnp.concatenate(new, axis=0))
    for r in range(rep):
        o_ref[:, r * dv:(r + 1) * dv] = o[r * c:(r + 1) * c]


def _chunk_bwd_kernel(q_ref, k_ref, v_ref, rows_ref, marks_ref, s_ref,
                      do_ref, dq_ref, dk_ref, dv_ref, drows_ref, dstate,
                      *, rep, c, dv, ct):
    """The chunks in reverse; ``dstate`` carries the cotangent of the
    state a chunk hands on."""
    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        dstate[...] = jnp.zeros_like(dstate)

    f32 = jnp.float32
    t = _chunk_tiles(q_ref, k_ref, v_ref, rows_ref, marks_ref, rep, c, dv, ct)
    q, k, v, kb, kk = t["q"], t["k"], t["v"], t["kb"], t["kk"]
    beta, dm, inv, tb = t["beta"], t["dm"], t["inv"], t["tb"]
    a_in, w_out = t["a_in"], t["w_out"]
    qb = q.astype(ct)
    pm = _dot(qb, kb, NT) * (dm + t["eye"])              # q k^T * D
    do = _stack(do_ref, rep, dv).astype(ct)
    sb = [s_ref[r].astype(ct) for r in range(rep)]
    new = jnp.concatenate(
        [(t["u"][r * c:(r + 1) * c]
          - _dot(t["w"][r * c:(r + 1) * c], sb[r])).astype(ct)
         for r in range(rep)], axis=0)
    # o = [q a] S + (q k^T * D) new
    dnew_o = _dot(pm.astype(ct), do, TN)
    dp = _dot(do, new, NT)
    du, dw, dqin, dkout, dkeep = [], [], [], [], []
    for r in range(rep):
        sl = slice(r * c, (r + 1) * c)
        ds = dstate[r]
        dsb = ds.astype(ct)
        # S' = keep S + (k decayed)^T new;  new = U - W S
        dnew = dnew_o[sl] + _dot(t["kout"][sl], dsb)
        dnewb = dnew.astype(ct)
        both = _dot(jnp.concatenate([do[sl], dnewb], axis=0), sb[r], NT)
        du.append(dnewb)
        dqin.append(both[:c])
        dw.append(-both[c:])
        dkout.append(_dot(new[sl], dsb, NT))
        dkeep.append(jnp.sum(_rowsum(ds * s_ref[r]), axis=0, keepdims=True))
        dstate[r] = t["keep"][r] * ds + _dot(t["qin"][sl], do[sl], TN) \
            - _dot(t["w"][sl], dnewb, TN)
    du = jnp.concatenate(du, axis=0)
    dw = jnp.concatenate(dw, axis=0).astype(ct)
    dqin = jnp.concatenate(dqin, axis=0).astype(ct).astype(f32)
    dkout = jnp.concatenate(dkout, axis=0).astype(ct).astype(f32)
    # U = T (beta v), W = T (beta a k)
    ba = beta * a_in
    dt = _dot(du, (beta * v).astype(ct), NT) \
        + _dot(dw, (ba * k).astype(ct), NT)
    dbv, dbk = _dot(tb, du, TN), _dot(tb, dw, TN)
    # T = (I + A)^{-1}: dA = -T^T dT T^T on the strict lower triangle of a
    # segment and head, which D's zeros keep
    da_d = -_dot(_dot(inv, dt, TN, HIGHEST), inv, NT, HIGHEST) * dm
    dkk = (da_d * beta).astype(ct)                       # A = beta kk D
    pa = da_d * kk
    e = pa * beta + dp * pm                              # dD * D, both D's
    s_k = _rowsum(dbk * k)
    dbeta = _rowsum(pa) + _rowsum(dbv * v) + a_in * s_k
    t_out = _rowsum(dkout * k) * w_out
    dcum = _rowsum(e) + (beta * s_k + _rowsum(dqin * q)) * a_in - t_out
    # G's last entry of each head took part in every row's decay to the
    # chunk's end and in the head's keep
    i, j = _ij(kk.shape[0])
    sh = c.bit_length() - 1
    ends = _colsum(jnp.where(((i >> sh) == (j >> sh))
                             & ((j & (c - 1)) == c - 1), t_out, 0.0))
    lane = jax.lax.broadcasted_iota(jnp.int32, ends.shape, 1)
    for r in range(rep):
        ends = ends + jnp.where(lane == (r + 1) * c - 1,
                                dkeep[r] * t["keep"][r], 0.0)
    drows_ref[0:1, :] = _row(dcum) - _colsum(e) + ends
    drows_ref[1:2, :] = _row(dbeta)
    dvs = (beta * dbv).astype(dv_ref.dtype)
    for r in range(rep):
        dv_ref[:, r * dv:(r + 1) * dv] = dvs[r * c:(r + 1) * c]
    dqk = (dp * (dm + t["eye"])).astype(ct)
    dk = ba * dbk + dkout * w_out + _dot(dkk, kb) + _dot(dkk, kb, TN) \
        + _dot(dqk, qb, TN)                              # kk = k k^T; q k^T
    dq = dqin * a_in + _dot(dqk, kb)
    dq_ref[...] = _fold(dq, rep).astype(dq_ref.dtype)
    dk_ref[...] = _fold(dk, rep).astype(dk_ref.dtype)


# ---- the calls ----------------------------------------------------------------

def _dims(k2, v2, rows):
    """(n, Hk, rep, c, dk, dv) from the operands' shapes."""
    n, hk, _, p = rows.shape
    c = k2.shape[0] // n
    rep = p // c
    return n, hk, rep, c, k2.shape[1] // hk, v2.shape[1] // (hk * rep)


def _call(kernel, name, backward, dims, operands, ins, outs, out_shape,
          interpret, ct):
    """The grid is (key head, chunk), the chunks one after the other (the
    state is VMEM scratch), last chunk first for the backward.  ``ins``
    and ``outs`` name each operand's block: a key head's [c, dk] columns
    (``qk``), its value heads' [c, rep dv] (``v``), the scalars' two rows,
    the chunk's marks, the value heads' states (``s``)."""
    n, hk, rep, c, dk, dv = dims
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    specs = {
        "qk": pl.BlockSpec((c, dk), lambda h, i: (at(i), h)),
        "v": pl.BlockSpec((c, rep * dv), lambda h, i: (at(i), h)),
        "rows": pl.BlockSpec((None, None, 2, rep * c),
                             lambda h, i: (at(i), h, 0, 0)),
        "marks": pl.BlockSpec((None, 3, rep * c),
                              lambda h, i: (at(i), 0, 0)),
        "s": pl.BlockSpec((None, rep, dk, dv),
                          lambda h, i: (at(i), h, 0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, rep=rep, c=c, dv=dv, ct=ct),
        grid=(hk, n),
        in_specs=[specs[x] for x in ins],
        out_specs=[specs[x] for x in outs], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(*operands)


# each call site is jitted on its own, so that a step of three layers,
# forward, recomputed and backward, traces and lowers a kernel once
@functools.partial(jax.jit, static_argnames=("ct", "interpret"))
def _chunks_fwd(q2, k2, v2, rows, marks, *, ct, interpret):
    """(o [T, Hv dv], the state each chunk starts from [n, Hv, dk, dv])."""
    dims = n, hk, rep, _, dk, dv = _dims(k2, v2, rows)
    return _call(
        _chunk_fwd_kernel, "gdn_chunk_fwd", False, dims,
        (q2, k2, v2, rows, marks), ["qk", "qk", "v", "rows", "marks"],
        ["v", "s"],
        [jax.ShapeDtypeStruct(v2.shape, jnp.float32),
         jax.ShapeDtypeStruct((n, hk * rep, dk, dv), jnp.float32)],
        interpret, ct)


@functools.partial(jax.jit, static_argnames=("ct", "interpret"))
def _chunks_bwd(q2, k2, v2, rows, marks, states, do, *, ct, interpret):
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return _call(
        _chunk_bwd_kernel, "gdn_chunk_bwd", True, _dims(k2, v2, rows),
        (q2, k2, v2, rows, marks, states, do),
        ["qk", "qk", "v", "rows", "marks", "s", "v"],
        ["qk", "qk", "v", "rows"],
        [like(q2), like(k2), like(v2), like(rows)], interpret, ct)


# ---- the differentiable function ----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_rule_chunks(q2, k2, v2, rows, marks, ct):
    """The chunked gated delta rule over a flat buffer of ``n`` chunks of
    ``c`` rows.  q2, k2 [T, Hk dk], v2 [T, Hv dv] (``T = n c``); rows [n,
    Hk, 2, rep c] float32: a key head's value heads' running sums of ``g``
    inside the chunk, a head after the other, and under them their betas;
    marks [n, 3, rep c] float32, the chunk's three rows once a value head:
    a row's segment as a number that is equal inside the chunk exactly
    where the ids are, whether it reads the incoming state (its segment
    began before the chunk), whether it writes the outgoing one (it is of
    the chunk's last segment).  Returns o [T, Hv dv] float32.

    Differentiated inside a ``topology.remat_scope``, the forward rule's
    residuals (these operands and the state each chunk starts from) and o
    are kept by name, so the backward pass runs ``gdn_chunk_bwd`` alone:
    1.0 GB a layer at the qwen3-next cell's shape against a second run of
    ``gdn_chunk_fwd`` and of the prologue before it."""
    return _chunks_fwd(q2, k2, v2, rows, marks, ct=ct,
                       interpret=interpret_default())[0]


def _chunks_vjp_fwd(q2, k2, v2, rows, marks, ct):
    o, states = _chunks_fwd(q2, k2, v2, rows, marks, ct=ct,
                            interpret=interpret_default())
    # under ``train.remat`` the segment keeps what the backward kernel
    # reads (``topology.KEPT``), tagged here on the residuals themselves:
    # o and the states, or this kernel runs a second time to rebuild
    # them, and the operands, or all that made them does.  The kernel
    # above takes the operands untagged: JAX rounds a kept value that the
    # forward pass goes on to read (``reduce_precision``, against excess
    # precision), which behind a kernel is a pass over it in HBM.
    return keep("gdn_scan", o), (
        *keep("gdn_operands", q2, k2, v2, rows, marks),
        keep("gdn_scan", states))


def _chunks_vjp_bwd(ct, res, do):
    return (*_chunks_bwd(*res, do, ct=ct, interpret=interpret_default()),
            None)


delta_rule_chunks.defvjp(_chunks_vjp_fwd, _chunks_vjp_bwd)
