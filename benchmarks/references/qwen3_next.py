"""Plain reference for the ``qwen3_next`` family (Qwen3-Next-80B-A3B):
forward pass, loss, gradient and Adam, in straightforward ``jax.numpy`` at
float32 with ``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no sort, no chunks: the gated delta rule is its
per-token recurrence (a ``lax.scan`` over the tokens, checkpointed in
blocks of rows so that the backward pass keeps one block's states);
attention is a dense softmax over the flat token buffer under a (same
document, not later) mask with the key/value heads repeated, made in
blocks of query rows; the expert layer is a loop over the experts that are
held, each applied to every row under a mask.

The equations (``x`` is a row of the float32 residual stream; every
sequence starts from zero state and zero convolution history):

- Block: ``x += Mix(N(x)); x += MoE(N(x))``, ``N`` a weighted RMSNorm;
  ``Mix`` is gated attention where the block has ``attn``, the gated
  delta-rule layer where it has ``delta``.  Final ``N``, an untied head
  without bias.
- Gated delta-rule layer (``Hk`` key heads, ``Hv`` value heads): ``[q | k |
  v | z] = x W_qkvz``, ``[b | a] = x W_ba``; ``[q | k | v] <-
  silu(conv(q | k | v))``, a causal depthwise convolution of 4 taps inside
  each sequence (the last tap meets the current token); ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k are
  L2-normalised per head (``x rsqrt(sum x^2 + 1e-6)``), q scaled by
  ``dk ** -0.5``, key head ``h // (Hv / Hk)`` serves value head ``h``.  Per
  value head, ``S`` in ``R^{dk x dv}`` from 0, token by token:
  ``S <- exp(g_t) S; r = v_t - S^T k_t; S <- S + k_t (beta_t r)^T;
  o_t = S^T q_t``.  Then ``y = RMSNorm(o_t) w_n silu(z_t)`` per head and
  ``out = y W_o``.
- Gated attention (``H`` query heads, ``H_kv`` key/value heads of ``D``):
  ``[q | gate] = x W_q`` per head; ``k = x W_k``, ``v = x W_v``; q and k
  through a weighted RMSNorm per head; RoPE (halves paired) on the first
  ``rotary`` dims of q and k; causal softmax inside a sequence of ``q k^T /
  sqrt(D)``, KV head ``h // (H / H_kv)`` for query head ``h``; ``out =
  (attention sigmoid(gate)) W_o``.
- Expert layer: ``p = softmax(x W_r)`` over all the experts (always
  float32); the top ``k`` are chosen with weights ``p_i / sum_chosen p``;
  ``y = sum_e w_e E_e(x) + sigmoid(x w_sg) E_shared(x)``, ``E(x) = (silu(x
  W_g) * (x W_u)) W_d``.  Of the routed experts only ``count`` from
  ``first`` on exist in the tree (one rank's share of an expert-parallel
  group): a chosen expert that is not held adds nothing.
- ``loss``: the next-token cross-entropy summed over the rows and divided
  by the number of sequences (the trainer's cost).

Weights are a tree made by ``harness/weights.py`` from flat names::

    wte [V, E], head [E, V], lnf_g [E], blocks: [block] * L
    block: ln1_g ln2_g [E], moe: {router [E, n], experts: {w_gate w_up
      [count, E, F], w_down [count, F, E]}, shared: {w_gate w_up w_down},
      shared_mix [E, 1]}, and
      delta: {w_qkvz w_ba conv [2 Hk dk + Hv dv, 4] a_log dt_bias [Hv]
        norm_g [dv] wo}
      or attn: {wq [E, H 2 D] wk wv [E, H_kv D] q_norm_g k_norm_g [D] wo}

``mode`` picks the arithmetic of every matrix product but the router's
(the recurrence's ``S^T k`` and ``S^T q`` among them): ``f32`` is the
reference; ``bf16`` rounds both operands to bfloat16 (what the program
states); ``fp8`` rounds both to float8 e4m3 under one scale per row and
column, the precision below, used only as the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
HIGHEST = jax.lax.Precision.HIGHEST


def _fake_fp8(x, axis):
    """Round to float8 e4m3 under one scale per slice along ``axis``;
    straight-through (the value is rounded, the gradient passes)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, mode: str):
    """``x @ w`` (the last axis of x by the last-but-one of w) with the
    operands rounded as ``mode`` says, accumulated in float32."""
    if mode == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        x = _fake_fp8(x, axis=-1)
        w = _fake_fp8(w, axis=-2)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}; one of {MODES}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, theta: float):
    """x [T, ..., d]: dimension i of the first half turns with dimension i
    of the second by ``position * theta ** (-2 i / d)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def swiglu(x, p, mode: str):
    return matmul(jax.nn.silu(matmul(x, p["w_gate"], mode))
                  * matmul(x, p["w_up"], mode), p["w_down"], mode)


# ---- the gated delta-rule layer ---------------------------------------------

def causal_conv(x, w, seg):
    """x [T, C], w [C, K]: ``y_t = sum_j w[:, j] x_{t - (K - 1) + j}``; a
    row that lies before its own sequence's start counts as zero."""
    t, taps = x.shape[0], w.shape[1]
    y = jnp.zeros_like(x)
    for back in range(taps):
        # the buffer moved ``back`` rows down, zeros moved in at the top
        moved = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        from_seg = jnp.pad(seg, (back, 0), constant_values=-1)[:t]
        y = y + jnp.where((from_seg == seg)[:, None], moved, 0.0) \
            * w[:, taps - 1 - back]
    return y


def delta_rule(q, k, v, g, beta, seg, mode: str, block_rows: int):
    """The recurrence, token by token.  q, k: [T, Hv, dk]; v: [T, Hv, dv];
    g, beta: [T, Hv]; seg [T].  Returns o [T, Hv, dv]."""
    t, hv, dv = v.shape
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t, first_t = x
        s = jnp.where(first_t, 0.0, s) * jnp.exp(g_t)[:, None, None]
        r = v_t - matmul(k_t[:, None, :], s, mode)[:, 0]       # S^T k
        s = s + k_t[:, :, None] * (b_t[:, None] * r)[:, None, :]
        return s, matmul(q_t[:, None, :], s, mode)[:, 0]       # S^T q

    @jax.checkpoint
    def rows(s, xs):
        return jax.lax.scan(token, s, xs)

    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)
    split = lambda a: a.reshape(  # noqa: E731
        (t // block_rows, block_rows) + a.shape[1:])
    _, o = jax.lax.scan(rows, jnp.zeros((hv, k.shape[-1], dv), jnp.float32),
                        tuple(split(a) for a in (q, k, v, g, beta, first)))
    return o.reshape(t, hv, dv)


def gated_delta_net(x, seg, p, *, k_heads: int, v_heads: int, dk: int,
                    dv: int, eps: float, mode: str, block_rows: int):
    t = x.shape[0]
    nq, nv = k_heads * dk, v_heads * dv

    # recomputed on its own in the backward pass, so that the [T, 12288]
    # projections and the convolution's copies are not kept beside the
    # recurrence's states
    @jax.checkpoint
    def inputs(x, p):
        qkvz = matmul(x, p["w_qkvz"], mode)
        ba = matmul(x, p["w_ba"], mode)
        qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * nq + nv], p["conv"], seg))
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
        rep = v_heads // k_heads
        q = jnp.repeat(unit(qkv[:, :nq].reshape(t, k_heads, dk)), rep,
                       axis=1) * dk ** -0.5
        k = jnp.repeat(unit(qkv[:, nq:2 * nq].reshape(t, k_heads, dk)), rep,
                       axis=1)
        v = qkv[:, 2 * nq:].reshape(t, v_heads, dv)
        beta = jax.nn.sigmoid(ba[:, :v_heads])
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, v_heads:]
                                                    + p["dt_bias"])
        return q, k, v, g, beta, qkvz[:, 2 * nq + nv:].reshape(t, v_heads, dv)

    q, k, v, g, beta, z = inputs(x, p)
    o = delta_rule(q, k, v, g, beta, seg, mode, block_rows)
    y = rms_norm(o, p["norm_g"], eps) * jax.nn.silu(z)
    return matmul(y.reshape(t, nv), p["wo"], mode)


# ---- gated attention -----------------------------------------------------------

def _attention(q, k, v, seg, block_rows: int, mode: str):
    """Dense causal attention inside documents.  q, k, v: [T, H, D]; seg
    [T]; rows are in order, so "not later" is the row index.  Scores are
    made ``block_rows`` query rows at a time."""
    t, h, dq = q.shape
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))
    rows = jnp.arange(t)
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qh, start, block_rows, axis=1)
        rb = jax.lax.dynamic_slice_in_dim(rows, start, block_rows)
        sb = jax.lax.dynamic_slice_in_dim(seg, start, block_rows)
        s = matmul(qb, kh.transpose(0, 2, 1), mode) * (dq ** -0.5)
        ok = (sb[:, None] == seg[None, :]) & (rows[None, :] <= rb[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return matmul(p, vh, mode)

    out = jax.lax.map(one_block, jnp.arange(0, t, block_rows))
    return out.transpose(0, 2, 1, 3).reshape(t, h * v.shape[-1])


def gated_attention(x, positions, seg, p, *, n_head: int, n_kv: int,
                    head_dim: int, rotary_dim: int, theta: float, eps: float,
                    mode: str, block_rows: int):
    t, d = x.shape[0], head_dim
    qg = matmul(x, p["wq"], mode).reshape(t, n_head, 2 * d)
    q = rms_norm(qg[..., :d], p["q_norm_g"], eps)
    k = rms_norm(matmul(x, p["wk"], mode).reshape(t, n_kv, d),
                 p["k_norm_g"], eps)
    v = matmul(x, p["wv"], mode).reshape(t, n_kv, d)

    def turn(a):
        return jnp.concatenate([rotary(a[..., :rotary_dim], positions, theta),
                                a[..., rotary_dim:]], axis=-1)

    wide = lambda a: jnp.repeat(a, n_head // n_kv, axis=1)  # noqa: E731
    ctx = _attention(turn(q), wide(turn(k)), wide(v), seg, block_rows, mode)
    gate = jax.nn.sigmoid(qg[..., d:]).reshape(t, n_head * d)
    return matmul(ctx * gate, p["wo"], mode)


# ---- the expert layer -------------------------------------------------------------

def route(x, p, top_k: int):
    """(experts [T, k], weights [T, k]): always float32, whatever the
    mode, as the published implementation has it."""
    probs = jax.nn.softmax(jnp.matmul(x, p["router"], precision=HIGHEST),
                           axis=-1)
    g, experts = jax.lax.top_k(probs, top_k)
    return experts, g / jnp.sum(g, axis=-1, keepdims=True)


def moe(x, p, *, top_k: int, first: int, mode: str, shared: bool = True):
    """The held experts' part of the layer (experts ``first ...`` of the
    router's, as many as the tree holds) plus, if ``shared``, the gated
    shared expert."""
    experts, g = route(x, p, top_k)
    ex = p["experts"]

    @jax.checkpoint
    def part(held):
        e, w_gate, w_up, w_down = held
        w = jnp.sum(jnp.where(experts == first + e, g, 0.0), axis=-1)
        out = swiglu(x, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                     mode)
        return w[:, None] * out

    # a loop over the held experts, one at a time, each over every row
    # (the running sum stays outside the recomputed part, so the backward
    # pass keeps no copy of it per expert)
    y, _ = jax.lax.scan(lambda y, held: (y + part(held), None),
                        jnp.zeros_like(x), (
        jnp.arange(ex["w_gate"].shape[0]), ex["w_gate"], ex["w_up"],
        ex["w_down"]))
    if not shared:
        return y
    return y + jax.nn.sigmoid(matmul(x, p["shared_mix"], mode)) \
        * swiglu(x, p["shared"], mode)


# ---- the model -----------------------------------------------------------------------

def block(x, positions, seg, b, arch: dict, mode: str, block_rows: int):
    eps = arch["eps"]
    a = rms_norm(x, b["ln1_g"], eps)
    if "attn" in b:
        a = gated_attention(
            a, positions, seg, b["attn"], n_head=arch["n_head"],
            n_kv=arch["n_kv"], head_dim=arch["head_dim"],
            rotary_dim=arch["rotary_dim"], theta=arch["theta"], eps=eps,
            mode=mode, block_rows=block_rows)
    else:
        a = gated_delta_net(
            a, seg, b["delta"], k_heads=arch["lin_k_heads"],
            v_heads=arch["lin_v_heads"], dk=arch["lin_dk"],
            dv=arch["lin_dv"], eps=eps, mode=mode, block_rows=block_rows)
    x = x + a
    return x + moe(rms_norm(x, b["ln2_g"], eps), b["moe"],
                   top_k=arch["top_k"], first=arch["first_held"], mode=mode)


def hidden(weights, tokens, positions, seg, arch: dict, mode: str = "f32",
           block_rows: int = 512):
    """The residual stream after the last block, before the final norm:
    [T, E].  Each block is recomputed in the backward pass."""
    x = weights["wte"][tokens]
    run = jax.checkpoint(functools.partial(
        block, arch=arch, mode=mode, block_rows=block_rows))
    for b in weights["blocks"]:
        x = run(x, positions, seg, b)
    return x


def _xent_sum(weights, x, targets, ok, arch, mode, head_rows):
    """Sum over the rows that are ``ok`` of the cross-entropy of
    ``head(RMSNorm(x))`` against ``targets``, ``head_rows`` rows at a time
    so that the [T, V] logits never exist."""
    t = x.shape[0]
    head_rows = min(head_rows, t)
    assert t % head_rows == 0, (t, head_rows)

    @jax.checkpoint
    def chunk(args):
        xb, tb, vb = args
        lg = matmul(rms_norm(xb, weights["lnf_g"], arch["eps"]),
                    weights["head"], mode)
        picked = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, jax.nn.logsumexp(lg, axis=-1) - picked,
                                 0.0))

    n = t // head_rows
    return jnp.sum(jax.lax.map(chunk, (x.reshape(n, head_rows, -1),
                                       targets.reshape(n, head_rows),
                                       ok.reshape(n, head_rows))))


def loss(weights, tokens, positions, targets, seg, valid, n_seqs, *,
         arch: dict, mode: str = "f32", block_rows: int = 512,
         head_rows: int = 2048):
    """The next-token loss summed over its rows and divided by
    ``n_seqs``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(weights, tokens, positions, seg, arch, mode, block_rows)
        return _xent_sum(weights, h, targets, valid, arch, mode,
                         head_rows) / n_seqs


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
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def make_train_step(*, arch: dict, mode: str, lr: float, b1: float,
                    b2: float, eps: float, reduce_grads,
                    block_rows: int = 512, head_rows: int = 2048):
    """One jitted reference step: (weights, m, v, step, key, tokens,
    positions, targets, seg, valid, n_seqs) -> (loss, reduce_grads(gradient
    tree, key), weights, m, v).  The old state is donated, so four trees
    (weights, m, v, gradient) are the peak."""
    loss_fn = functools.partial(loss, arch=arch, mode=mode,
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
