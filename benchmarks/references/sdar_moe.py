"""Plain reference for the ``sdar_moe`` family (SDAR-30B-A3B-Chat: a
Qwen3-MoE decoder that generates by diffusion over blocks), in
straightforward ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no sorting of rows by expert: attention is a dense
softmax over the flat token buffer, computed in blocks of query rows so
the score matrix fits, and the expert layer runs every expert over every
row and weights the eight chosen ones.

The model.  Block ``l`` on rows ``x [T, E]`` at positions ``p``::

    h = rms_norm(x; ln1_g)
    q = h wq -> [T, H, D];  k = h wk, v = h wv -> [T, H_kv, D]   (no biases)
    q = rms_norm(q; q_g), k = rms_norm(k; k_g)     over the D lanes of a head
    rotary on all D lanes of q and k (theta, half-split pairing)
    query head i reads KV head i // (H / H_kv); scores / sqrt(D)
    a query at position p sees key j iff j // B <= p // B
    a = x + ctx wo
    h2 = rms_norm(a; ln2_g);  r = softmax(h2 router) over the experts
    the top_k largest, their weights renormalised to sum to 1
    y = sum_e w_e (silu(h2 w_gate[e]) * (h2 w_up[e])) w_down[e]
    out = a + y

and the head is ``rms_norm(x; norm_g) head``, untied.  Row ``p``'s logits
judge the token AT position ``p`` (no shift).  ``B`` is the block length:
the mask is causal over blocks and sees both ways inside one.

Generation (the family's generate script, ``sequential`` remasking): the
sequence is cut into blocks of ``B`` at absolute positions; the prompt's
whole blocks are clean; then block by block, the block's unfilled
positions hold ``mask_token_id``, and each denoising pass runs the block
against the clean earlier blocks and itself and fixes the next ``B / S``
masked positions from the left to their best token, the mask token
excluded.  So the state in which a position was fixed is: every earlier
block clean, its own block clean below some offset ``m`` and masked from
``m`` on.  :func:`hidden` computes that for every block at once, in two
kinds of stream: one clean copy of the sequence, and for each asked ``m``
a copy whose blocks are masked from their offset ``m`` on and whose rows
read the CLEAN copy's earlier blocks and their own copy's block.

Weights are a canonical tree made by ``harness/weights.py``::

    {"wte": [V, E], "head": [E, V], "norm_g": [E], "blocks": [ {...} ] * L}
    block: ln1_g ln2_g [E], wq [E, H D], wk wv [E, H_kv D], wo [H D, E],
           q_g k_g [D], router [E, N], w_gate w_up [N, E, F],
           w_down [N, F, E]

``mode`` picks the arithmetic of every matrix product: ``f32`` is the
reference; ``bf16`` rounds both operands to bfloat16 (what the program
states); ``fp8`` rounds both to float8 e4m3 with one scale per row of the
left operand and per column of the right, the precision below the stated
one, used only as the control of ``correct``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, theta: float):
    """x [..., T, heads, D]; positions [T].  Lane ``i`` of the first half
    turns with lane ``i`` of the second by ``position * theta ** (-2 i /
    D)`` (the ``rotate_half`` pairing)."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, positions, block: int, block_rows: int, mode: str):
    """q [n, T, H, D]; k, v [n, T, H_kv, D]: stream 0 is the clean copy.
    A row of stream i at position p sees the clean copy's keys in earlier
    blocks (``j // B < p // B``) and its OWN stream's keys in its block
    (``j // B == p // B``); for the clean copy that is the block-causal
    mask itself.  Scores are made ``block_rows`` query rows at a time."""
    n, t, h, d = q.shape
    kvh = k.shape[2]
    blk = positions // block                                  # [T]
    # [n, T, H_kv, D] -> heads first, each KV head under its H / H_kv
    # query heads
    kc = jnp.repeat(k[0], h // kvh, axis=1).transpose(1, 0, 2)   # [H, T, D]
    vc = jnp.repeat(v[0], h // kvh, axis=1).transpose(1, 0, 2)
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    def scores(a, b):
        if mode == "f32":
            return jnp.einsum("hqd,hkd->hqk", a, b,
                              precision=jax.lax.Precision.HIGHEST)
        return matmul(a, b.transpose(0, 2, 1), mode)

    def mix(p, b):
        if mode == "f32":
            return jnp.einsum("hqk,hkd->hqd", p, b,
                              precision=jax.lax.Precision.HIGHEST)
        return matmul(p, b, mode)

    def stream(qs, ks, vs):
        qh = qs.transpose(1, 0, 2)                            # [H, T, D]
        ko = jnp.repeat(ks, h // kvh, axis=1).transpose(1, 0, 2)
        vo = jnp.repeat(vs, h // kvh, axis=1).transpose(1, 0, 2)

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block_rows, axis=1)
            bb = jax.lax.dynamic_slice_in_dim(blk, start, block_rows)
            s = jnp.concatenate([scores(qb, kc), scores(qb, ko)], axis=-1)
            s = s * (d ** -0.5)
            ok = jnp.concatenate([blk[None, :] < bb[:, None],
                                  blk[None, :] == bb[:, None]], axis=-1)
            p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
            return mix(p[..., :t], vc) + mix(p[..., t:], vo)

        out = jax.lax.map(one_block, jnp.arange(0, t, block_rows))
        return out.transpose(0, 2, 1, 3).reshape(t, h * d)    # [T, H D]

    return jnp.stack([stream(q[i], k[i], v[i]) for i in range(n)])


def experts(h, b, top_k: int, mode: str):
    """The expert layer on rows h [R, E]: softmax over the router's
    scores in float32, the ``top_k`` largest renormalised, and every
    expert run over every row, one expert at a time, weighted by what the
    row gave it (0 where it was not chosen)."""
    r = jax.nn.softmax(matmul(h, b["router"], mode), axis=-1)   # [R, N]
    top, chosen = jax.lax.top_k(r, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    n = r.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(chosen, n, dtype=jnp.float32)
                     * top[..., None], axis=1)                  # [R, N]

    def one(y, e):
        wg, wu, wd, w = e
        act = jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode)
        return y + w[:, None] * matmul(act, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (b["w_gate"], b["w_up"], b["w_down"], weight.T))
    return y


def hidden(weights, tokens, positions, masked_from, *, n_head: int,
           n_kv_head: int, head_dim: int, top_k: int, block: int,
           mask_token_id: int, theta: float, eps: float,
           mode: str = "f32", block_rows: int = 256):
    """The residual stream after the last block, before the final norm,
    of every masked copy: ``[n, T, E]`` for ``masked_from [n, T]`` (copy
    ``i`` holds ``mask_token_id`` at every position whose offset in its
    block is ``masked_from[i, p]`` or more, ``masked_from`` constant
    inside a block; it reads the clean copy's earlier blocks and its own
    block)."""
    off = positions % block
    masked = jnp.where(off[None, :] >= masked_from, mask_token_id,
                       tokens[None, :])
    x = weights["wte"][jnp.concatenate([tokens[None, :], masked])]
    n, t, e = x.shape
    for b in weights["blocks"]:
        h = rms_norm(x, b["ln1_g"], eps)
        q = matmul(h, b["wq"], mode).reshape(n, t, n_head, head_dim)
        k = matmul(h, b["wk"], mode).reshape(n, t, n_kv_head, head_dim)
        v = matmul(h, b["wv"], mode).reshape(n, t, n_kv_head, head_dim)
        q = rotary(rms_norm(q, b["q_g"], eps), positions, theta)
        k = rotary(rms_norm(k, b["k_g"], eps), positions, theta)
        ctx = _attention(q, k, v, positions, block, block_rows, mode)
        a = x + matmul(ctx, b["wo"], mode)
        h2 = rms_norm(a, b["ln2_g"], eps)
        x = a + experts(h2.reshape(n * t, e), b, top_k, mode
                        ).reshape(n, t, e)
    return x[1:]


def logits_of(weights, x, *, eps: float, mode: str = "f32"):
    return matmul(rms_norm(x, weights["norm_g"], eps), weights["head"], mode)


def state_logits(weights, tokens, positions, masked_from, state, *,
                 mode: str = "f32", block_rows: int = 256, **arch):
    """Logits ``[T, V]`` whose row ``p`` is row ``p`` of the masked copy
    ``state[p]`` (``masked_from [n, T]`` as :func:`hidden` takes it): the
    state in which position ``p`` was fixed."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, positions, masked_from, mode=mode,
                   block_rows=block_rows, **arch)
        mine = jnp.take_along_axis(x, state[None, :, None], axis=0)[0]
        return logits_of(weights, mine, eps=arch["eps"], mode=mode)
