"""Plain reference for the ``ouro`` family (Ouro: a looped language model,
a decoder whose whole stack runs several times over the SAME weights), in
straightforward ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no batching, nothing handed between calls: every pass
is a full forward of the stack over the whole token buffer, attention a
dense softmax under the causal mask, computed ``block_rows`` query rows
and one head at a time so the score matrix fits.

The model.  ``N(x; g) = g * x / sqrt(mean(x^2) + eps)``, the same eps
everywhere.  With ``L`` layers and ``T`` passes (``total_ut_steps``)::

    h_0     = wte[token]                                  (no scale)
    for t = 1..T:                  the SAME leaves in every pass
      x = h_{t-1}
      for l = 1..L:
        u       = N(x; norm1_g)
        q, k, v = u wq, u wk, u wv          H heads of D lanes, no bias
        q, k    = rotary(q), rotary(k)      all D lanes, half-split pairing
                                            (lane i with i + D / 2),
                                            theta ** (-2 i / D), unscaled
        c       = causal_softmax(q k^T / sqrt(D)) v       THIS pass's k, v:
                  position p at (t, l) sees positions <= p at (t, l)
        a       = x + N(c wo; norm2_g)
        u       = N(a; norm3_g)
        x       = a + N((silu(u ffn_gate) * (u ffn_up)) ffn_down; norm4_g)
      h_t   = N(x; norm_g)        the final norm closes EVERY pass
      lam_t = sigmoid(h_t . gate_w + gate_b)              the exit gate

    p_t = lam_t prod_{j<t} (1 - lam_j)   for t < T
    p_T = prod_{j<T} (1 - lam_j)         (the last pass takes what is left)

A token leaves at the first ``t`` whose ``p_1 + .. + p_t`` reaches
``early_exit_threshold``; at the published 1.0 that is ``T`` for every
token, and the served logits are ``h_T head`` (``h_T`` is already under
the final norm; the head is untied).  Row ``p``'s logits judge the token
at position ``p + 1``.

Weights are a canonical tree made by ``harness/weights.py``::

    {"wte": [V, E], "head": [E, V], "norm_g": [E], "gate_w": [E, 1],
     "gate_b": [1], "blocks": [ {...} ] * L}
    block: norm1_g norm2_g norm3_g norm4_g [E], wq wk wv [E, H D],
           wo [H D, E], ffn_gate ffn_up [E, F], ffn_down [F, E]

``mode`` picks the arithmetic of every matrix product (the projections,
attention's two products, the MLP, the head): ``f32`` is the reference;
``bf16`` rounds both operands to bfloat16 (what the program states);
``fp8`` rounds both to float8 e4m3 with one scale per row of the left
operand and per column of the right, the precision below the stated one,
used only as the control of ``correct``.  The norms, the softmax and the
gate are float32 in every mode.

:class:`RowLogits`.  The check reads the rows of the served tokens only,
so :func:`logits` returns the final hidden rows with the head beside
them, and the head's product is made for the rows that are asked for
(``result[a:b]``: ``[b - a, V]``, computed like every other product of
``mode``).  :func:`forward` returns, beside the last pass's, every
pass's :class:`RowLogits` and the exit distribution ``p [T, rows]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16", "fp8")


def _fake_fp8(x, axis):
    """Round to float8 e4m3 (3 bits of mantissa, largest value 448) under
    one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, mode: str):
    """``x @ w`` with the operands rounded as ``mode`` says, accumulated
    in float32 at the highest precision."""
    if mode == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        x = _fake_fp8(x, axis=-1)
        w = _fake_fp8(w, axis=-2)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, g, eps: float):
    return g * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def rotary(x, positions, theta: float):
    """x [T, heads, D]; positions [T].  Lane ``i`` turns with lane ``i + D
    / 2`` by ``position * theta ** (-2 i / D)`` (the ``rotate_half``
    pairing)."""
    d = x.shape[-1]
    inv = (float(theta) ** (-np.arange(0, d, 2, dtype=np.float32) / d)
           ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, b, positions, *, n_head: int, head_dim: int, theta: float,
              block_rows: int, mode: str):
    """The attention context ``[T, H D]`` of one layer in one pass from
    its normalised input ``u [T, E]``: K and V for every row, then the
    rows' queries and scores ``block_rows`` at a time, a head after the
    other."""
    t = u.shape[0]
    k = rotary(matmul(u, b["wk"], mode).reshape(t, n_head, head_dim),
               positions, theta)
    v = matmul(u, b["wv"], mode).reshape(t, n_head, head_dim)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)        # [H, T, D]
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    def one_block(start):
        ub = jax.lax.dynamic_slice_in_dim(u, start, block_rows)
        pb = jax.lax.dynamic_slice_in_dim(positions, start, block_rows)
        q = rotary(matmul(ub, b["wq"], mode).reshape(block_rows, n_head,
                                                     head_dim), pb, theta)
        ok = positions[None, :] <= pb[:, None]

        def one_head(args):
            qh, kh, vh = args
            s = matmul(qh, kh.T, mode) * (head_dim ** -0.5)
            return matmul(jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1),
                          vh, mode)

        ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2), kt, vt))
        return ctx.transpose(1, 0, 2).reshape(block_rows, n_head * head_dim)

    out = jax.lax.map(one_block, jnp.arange(0, t, block_rows))
    return out.reshape(t, n_head * head_dim)


def one_pass(weights, x, positions, *, n_head: int, head_dim: int,
             theta: float, eps: float, mode: str, block_rows: int):
    """The whole stack once over ``x [T, E]``, the final norm behind it."""
    for b in weights["blocks"]:
        ctx = attention(rms_norm(x, b["norm1_g"], eps), b, positions,
                        n_head=n_head, head_dim=head_dim, theta=theta,
                        block_rows=block_rows, mode=mode)
        a = x + rms_norm(matmul(ctx, b["wo"], mode), b["norm2_g"], eps)
        u = rms_norm(a, b["norm3_g"], eps)
        y = matmul(jax.nn.silu(matmul(u, b["ffn_gate"], mode))
                   * matmul(u, b["ffn_up"], mode), b["ffn_down"], mode)
        x = a + rms_norm(y, b["norm4_g"], eps)
    return rms_norm(x, weights["norm_g"], eps)


def passes(weights, tokens, positions, *, steps: int, mode: str = "f32",
           block_rows: int = 256, **arch):
    """``[h_1, .., h_T]``, each ``[rows, E]``: every pass's output under
    the final norm.  The passes are one ``lax.scan`` (the same leaves in
    every pass: the body is the whole stack, traced once)."""
    def again(x, _):
        x = one_pass(weights, x, positions, mode=mode, block_rows=block_rows,
                     **arch)
        return x, x

    _, out = jax.lax.scan(again, weights["wte"][tokens], None, length=steps)
    return list(out)


def exit_distribution(weights, hs):
    """``p [T, rows]`` from every pass's rows ``hs``: the module's doc."""
    lam = jnp.stack([jax.nn.sigmoid(
        jnp.matmul(h, weights["gate_w"],
                   precision=jax.lax.Precision.HIGHEST)[:, 0]
        + weights["gate_b"][0]) for h in hs])
    p, left = [], jnp.ones_like(lam[0])
    for t in range(len(hs) - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def exit_step(p, threshold: float):
    """The pass (1-based) each row leaves at: the first whose cumulative
    ``p`` reaches ``threshold``, the last where none does (rounding)."""
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0),
                     jnp.argmax(reached, axis=0) + 1, p.shape[0])


@jax.tree_util.register_pytree_node_class
class RowLogits:
    """The logits of every row, the head's product left for the rows that
    are asked for: ``rows[a:b]`` is ``[b - a, V]`` (the module's doc)."""

    def __init__(self, final, head, mode: str):
        self.final, self.head, self.mode = final, head, mode

    def tree_flatten(self):
        return (self.final, self.head), (self.mode,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def shape(self):
        return (self.final.shape[0], self.head.shape[1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return matmul(self.final[rows], self.head, self.mode)


def forward(weights, tokens, positions, *, steps: int, mode: str = "f32",
            block_rows: int = 256, **arch) -> dict:
    """``{"logits": the last pass's RowLogits, "pass_logits": every
    pass's, "exit_p": [T, rows]}``."""
    with jax.default_matmul_precision("highest"):
        hs = passes(weights, tokens, positions, steps=steps, mode=mode,
                    block_rows=block_rows, **arch)
        rows = [RowLogits(h, weights["head"], mode) for h in hs]
        return {"logits": rows[-1], "pass_logits": rows,
                "exit_p": exit_distribution(weights, hs)}


def logits(weights, tokens, positions, *, steps: int, mode: str = "f32",
           block_rows: int = 256, **arch) -> RowLogits:
    """:class:`RowLogits` of the last pass over the buffer: row ``p``
    judges the token at position ``p + 1``."""
    with jax.default_matmul_precision("highest"):
        return RowLogits(
            passes(weights, tokens, positions, steps=steps, mode=mode,
                   block_rows=block_rows, **arch)[-1],
            weights["head"], mode)
