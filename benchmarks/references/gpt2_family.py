"""Plain reference for the GPT-2 family (pre-LN decoder, learned positions,
GELU): forward pass, loss, gradient and Adam, in straightforward
``jax.numpy`` at float32 with ``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no batching across sequences: attention is a dense
softmax over the flat token buffer under a (same document, not later)
mask, computed in blocks of query rows so the score matrix fits.

Weights are a canonical tree made by ``harness/weights.py``::

    {"wte": [V, E], "wpe": [P, E], "blocks": [ {...} ] * L,
     "lnf_g": [E] | absent, "lnf_b": [E] | absent,
     "head": [E, V] | absent (tied to wte), "head_b": [V] | absent}
    block: wq wk wv wo [E, E], w1 [E, F], w2 [F, E], and where the
    architecture has them bq bk bv bo b1 b2, ln1_g ln1_b ln2_g ln2_b

The departures of the two programs from the published architecture are
arguments (``arch``): ``norm`` is ``layernorm`` (gain and bias, eps 1e-5)
or ``rms_noparam`` (x * rsqrt(mean(x^2) + 1e-6), no parameters); a bias or
a head that is absent from the tree is simply not applied.

``mode`` picks the arithmetic of every matrix product: ``f32`` is the
reference; ``bf16`` rounds both operands to bfloat16 (what the programs
state); ``fp8`` rounds both to float8 e4m3 with one scale per row of the
left operand and per column of the right, the precision below the stated
one, used only as the control of ``correct``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")


def _fake_fp8(x, axis):
    """Round to float8 e4m3 (3 bits of mantissa, largest value 448) under
    one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # straight-through: the value is rounded, the gradient passes
    return x + jax.lax.stop_gradient(q - x)


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


def _norm(x, g, b, kind: str):
    if kind == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
    if kind == "rms_noparam":
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    raise ValueError(f"unknown norm {kind!r}")


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, seg, n_head: int, block_rows: int, mode: str):
    """Dense causal attention inside documents.  q, k, v: [T, E]; seg: [T]
    document index per row (rows are in order, so "not later" is the row
    index).  Scores are made ``block_rows`` query rows at a time."""
    t, e = q.shape
    d = e // n_head
    qh = q.reshape(t, n_head, d).transpose(1, 0, 2)
    kh = k.reshape(t, n_head, d).transpose(1, 0, 2)
    vh = v.reshape(t, n_head, d).transpose(1, 0, 2)
    rows = jnp.arange(t)
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qh, start, block_rows, axis=1)
        rb = jax.lax.dynamic_slice_in_dim(rows, start, block_rows)
        sb = jax.lax.dynamic_slice_in_dim(seg, start, block_rows)
        if mode == "f32":
            s = jnp.einsum("hqd,hkd->hqk", qb, kh,
                           precision=jax.lax.Precision.HIGHEST)
        else:
            s = matmul(qb, kh.transpose(0, 2, 1), mode)
        s = s * (d ** -0.5)
        ok = (sb[:, None] == seg[None, :]) & (rows[None, :] <= rb[:, None])
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if mode == "f32":
            return jnp.einsum("hqk,hkd->hqd", p, vh,
                              precision=jax.lax.Precision.HIGHEST)
        return matmul(p, vh, mode)

    starts = jnp.arange(0, t, block_rows)
    out = jax.lax.map(one_block, starts)          # [nb, H, block, d]
    return out.transpose(0, 2, 1, 3).reshape(t, e)


def hidden(weights, tokens, positions, seg, *, n_head: int, norm: str,
           mode: str = "f32", block_rows: int = 512):
    """The residual stream after the last block, before the final norm:
    [T, E].  Each block is recomputed in the backward pass."""
    x = weights["wte"][tokens] + weights["wpe"][positions]

    @jax.checkpoint
    def block(x, b):
        a = _norm(x, b.get("ln1_g"), b.get("ln1_b"), norm)
        q = matmul(a, b["wq"], mode) + b.get("bq", 0.0)
        k = matmul(a, b["wk"], mode) + b.get("bk", 0.0)
        v = matmul(a, b["wv"], mode) + b.get("bv", 0.0)
        ctx = _attention(q, k, v, seg, n_head, block_rows, mode)
        x = x + matmul(ctx, b["wo"], mode) + b.get("bo", 0.0)
        f = _norm(x, b.get("ln2_g"), b.get("ln2_b"), norm)
        f = _gelu_tanh(matmul(f, b["w1"], mode) + b.get("b1", 0.0))
        return x + matmul(f, b["w2"], mode) + b.get("b2", 0.0)

    for b in weights["blocks"]:
        x = block(x, b)
    return x


def logits_of(weights, x, *, norm: str, mode: str = "f32"):
    x = _norm(x, weights.get("lnf_g"), weights.get("lnf_b"), norm)
    head = weights["head"] if "head" in weights else weights["wte"].T
    return matmul(x, head, mode) + weights.get("head_b", 0.0)


def forward_logits(weights, tokens, positions, seg, *, n_head: int,
                   norm: str, mode: str = "f32", block_rows: int = 512):
    """Next-token logits at every row: [T, V]."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, positions, seg, n_head=n_head, norm=norm,
                   mode=mode, block_rows=block_rows)
        return logits_of(weights, x, norm=norm, mode=mode)


def loss(weights, tokens, positions, targets, seg, valid, n_seqs, *,
         n_head: int, norm: str, mode: str = "f32", block_rows: int = 512,
         head_rows: int = 2048):
    """Summed next-token cross-entropy over the valid rows, divided by the
    number of documents (the trainers' cost).  The head and the softmax
    run ``head_rows`` rows at a time so the [T, V] logits never exist."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, positions, seg, n_head=n_head, norm=norm,
                   mode=mode, block_rows=block_rows)
        t = x.shape[0]
        head_rows = min(head_rows, t)
        assert t % head_rows == 0, (t, head_rows)

        @jax.checkpoint
        def chunk(args):
            xb, tb, vb = args
            lg = logits_of(weights, xb, norm=norm, mode=mode)
            lse = jax.nn.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(vb, lse - picked, 0.0))

        n = t // head_rows
        parts = jax.lax.map(chunk, (x.reshape(n, head_rows, -1),
                                    targets.reshape(n, head_rows),
                                    valid.reshape(n, head_rows)))
        return jnp.sum(parts) / n_seqs


def adam_step(weights, m, v, grads, step, *, lr: float, b1: float,
              b2: float, eps: float):
    """Adam as published (bias-corrected, no decay).  ``step`` counts from
    0.  Returns (weights, m, v)."""
    t = jnp.asarray(step, jnp.float32) + 1.0

    def one(p, m_, v_, g):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * jnp.square(g)
        mhat = m2 / (1 - jnp.power(b1, t))
        vhat = v2 / (1 - jnp.power(b2, t))
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m2, v2

    out = jax.tree.map(one, weights, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def make_train_step(*, n_head: int, norm: str, mode: str, lr: float,
                    b1: float, b2: float, eps: float, reduce_grads,
                    block_rows: int = 512, head_rows: int = 2048):
    """One jitted reference step: (weights, m, v, step, batch) ->
    (loss, reduce_grads(gradient tree, key), weights, m, v).
    ``reduce_grads`` turns the gradient into the few numbers that are
    compared; ``key`` is passed through to it.  The old
    state is donated so four trees (weights, m, v, gradient) are the
    peak."""
    loss_fn = functools.partial(loss, n_head=n_head, norm=norm, mode=mode,
                                block_rows=block_rows, head_rows=head_rows)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step_fn(weights, m, v, step, key, tokens, positions, targets, seg,
                valid, n_seqs):
        value, grads = jax.value_and_grad(loss_fn)(
            weights, tokens, positions, targets, seg, valid, n_seqs)
        reduced = reduce_grads(grads, key)
        weights, m, v = adam_step(weights, m, v, grads, step, lr=lr, b1=b1,
                                  b2=b2, eps=eps)
        return value, reduced, weights, m, v

    return step_fn
