"""Plain reference for the ``glm4_moe_lite`` family (GLM-4.7-Flash; the block
is DeepSeek-V3's): forward pass, loss, gradient and Adam, in straightforward
``jax.numpy`` at float32 with ``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no sort: attention is a dense softmax over the flat
token buffer under a (same document, not later) mask, made in blocks of
query rows; the expert layer is a loop over the experts that are held,
each applied to every row under a mask.

The equations (``x`` is a row of the float32 residual stream):

- MLA: ``c_q = RMSNorm_w(x W_qa)``; ``q = c_q W_qb`` -> H x (nope | rope).
  ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm_w(c_kv)``; ``[k_nope | v] =
  c_kv W_kvb`` -> H x (nope | v).  RoPE (theta, the whole rotary width,
  halves paired) on each head's ``q_rope`` and on ``k_r``, which all heads
  share.  ``k = [k_nope | k_r]``; causal softmax inside a sequence of ``q
  k^T / sqrt(nope + rope)``; heads concatenated, times ``W_o``.  No biases.
- Expert layer: ``s = sigmoid(x W_r)`` (always float32); the top ``k`` of
  ``s + b`` are chosen; ``g = s[chosen] / sum(s[chosen]) * scaling``;
  ``y = sum_e g_e E_e(x) + E_shared(x)``, ``E(x) = (silu(x W_g) * (x W_u))
  W_d``.  Of the routed experts only ``held = (first, count)`` exist in the
  tree (one rank's share of an expert-parallel group): a chosen expert
  that is not held adds nothing.  ``b`` gets no gradient (it only chooses).
- Block: ``x += MLA(RMSNorm_w(x))``; ``x += FFN(RMSNorm_w(x))``; the FFN is
  a dense SwiGLU where the block has ``ffn``, the expert layer where it
  has ``moe``.  Final ``RMSNorm_w``, an untied head without bias.
- MTP (depth 1): ``h' = [RMSNorm_w(h_i) | RMSNorm_w(Emb(t_{i+1}))] W_eh``
  with ``h_i`` the trunk's output before the final norm; one expert block;
  the module's own norm and the main model's head predict ``t_{i+2}``;
  rows whose ``t_{i+2}`` lies beyond their sequence are masked.
  ``loss = main + mtp_weight * MTP``, each a sum over rows divided by the
  number of sequences (the trainer's cost).

Weights are a tree made by ``harness/weights.py`` from flat names::

    wte [V, E], head [E, V], lnf_g [E], blocks: [block] * L,
    mtp: {hnorm_g, enorm_g [E], eh_proj [2E, E], norm_g [E], block}
    block: ln1_g ln2_g [E], attn: {wq_a q_norm_g wq_b wkv_a kv_norm_g
      wkv_b wo}, and ffn: {w_gate w_up w_down} or moe: {router [E, n],
      bias [n], experts: {w_gate w_up [count, E, F], w_down [count, F,
      E]}, shared: {w_gate w_up w_down}}

``mode`` picks the arithmetic of every matrix product but the router's:
``f32`` is the reference; ``bf16`` rounds both operands to bfloat16 (what
the program states); ``fp8`` rounds both to float8 e4m3 under one scale
per row and column, the precision below, used only as the control.
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


def _attention(q, k, v, seg, block_rows: int, mode: str):
    """Dense causal attention inside documents.  q, k: [T, H, dq]; v: [T,
    H, dv]; seg [T]; rows are in order, so "not later" is the row index.
    Scores are made ``block_rows`` query rows at a time."""
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


def mla(x, positions, seg, p, *, n_head: int, nope: int, rope: int,
        theta: float, eps: float, mode: str, block_rows: int):
    t = x.shape[0]
    rank = p["kv_norm_g"].shape[0]
    cq = rms_norm(matmul(x, p["wq_a"], mode), p["q_norm_g"], eps)
    q = matmul(cq, p["wq_b"], mode).reshape(t, n_head, nope + rope)
    ckv = matmul(x, p["wkv_a"], mode)
    c = rms_norm(ckv[:, :rank], p["kv_norm_g"], eps)
    kv = matmul(c, p["wkv_b"], mode).reshape(t, n_head, -1)
    k_r = rotary(ckv[:, None, rank:], positions, theta)
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], positions, theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, n_head, rope))], axis=-1)
    ctx = _attention(q, k, kv[..., nope:], seg, block_rows, mode)
    return matmul(ctx, p["wo"], mode)


def route(x, p, top_k: int, scaling: float):
    """(experts [T, k], weights [T, k]): always float32, whatever the
    mode, as the published implementation has it."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, experts = jax.lax.top_k(s + p["bias"], top_k)
    g = jnp.take_along_axis(s, experts, axis=-1)
    return experts, g / jnp.sum(g, axis=-1, keepdims=True) * scaling


def moe(x, p, *, top_k: int, scaling: float, first: int, mode: str,
        shared: bool = True):
    """The held experts' part of the layer (experts ``first ...`` of the
    router's, as many as the tree holds) plus, if ``shared``, the shared
    expert."""
    experts, g = route(x, p, top_k, scaling)
    ex = p["experts"]

    @jax.checkpoint
    def one(y, held):
        e, w_gate, w_up, w_down = held
        w = jnp.sum(jnp.where(experts == first + e, g, 0.0), axis=-1)
        out = swiglu(x, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                     mode)
        return y + w[:, None] * out, None

    # a loop over the held experts, one at a time, each over every row
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(ex["w_gate"].shape[0]), ex["w_gate"], ex["w_up"],
        ex["w_down"]))
    return y + swiglu(x, p["shared"], mode) if shared else y


def block(x, positions, seg, b, arch: dict, mode: str, block_rows: int):
    eps = arch["eps"]
    a = mla(rms_norm(x, b["ln1_g"], eps), positions, seg, b["attn"],
            n_head=arch["n_head"], nope=arch["nope"], rope=arch["rope"],
            theta=arch["theta"], eps=eps, mode=mode, block_rows=block_rows)
    x = x + a
    f = rms_norm(x, b["ln2_g"], eps)
    if "ffn" in b:
        return x + swiglu(f, b["ffn"], mode)
    return x + moe(f, b["moe"], top_k=arch["top_k"], scaling=arch["scaling"],
                   first=arch["first_held"], mode=mode)


def hidden(weights, tokens, positions, seg, arch: dict, mode: str = "f32",
           block_rows: int = 512):
    """The trunk's residual stream after the last block, before the final
    norm: [T, E].  Each block is recomputed in the backward pass."""
    x = weights["wte"][tokens]
    run = jax.checkpoint(functools.partial(
        block, arch=arch, mode=mode, block_rows=block_rows))
    for b in weights["blocks"]:
        x = run(x, positions, seg, b)
    return x


def mtp_hidden(weights, h, next_tokens, positions, seg, arch: dict,
               mode: str = "f32", block_rows: int = 512):
    """The MTP module's residual stream before its own norm."""
    m = weights["mtp"]
    cat = jnp.concatenate(
        [rms_norm(h, m["hnorm_g"], arch["eps"]),
         rms_norm(weights["wte"][next_tokens], m["enorm_g"], arch["eps"])],
        axis=-1)
    x = matmul(cat, m["eh_proj"], mode)
    return jax.checkpoint(functools.partial(
        block, arch=arch, mode=mode, block_rows=block_rows))(
        x, positions, seg, m["block"])


def _xent_sum(weights, x, norm_g, targets, ok, arch, mode, head_rows):
    """Sum over the rows that are ``ok`` of the cross-entropy of
    ``head(RMSNorm(x))`` against ``targets``, ``head_rows`` rows at a time
    so that the [T, V] logits never exist."""
    t = x.shape[0]
    head_rows = min(head_rows, t)
    assert t % head_rows == 0, (t, head_rows)

    @jax.checkpoint
    def chunk(args):
        xb, tb, vb = args
        lg = matmul(rms_norm(xb, norm_g, arch["eps"]), weights["head"], mode)
        picked = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, jax.nn.logsumexp(lg, axis=-1) - picked,
                                 0.0))

    n = t // head_rows
    return jnp.sum(jax.lax.map(chunk, (x.reshape(n, head_rows, -1),
                                       targets.reshape(n, head_rows),
                                       ok.reshape(n, head_rows))))


def losses(weights, tokens, positions, targets, seg, valid, n_seqs, *,
           arch: dict, mode: str = "f32", block_rows: int = 512,
           head_rows: int = 2048):
    """(next-token loss, MTP loss before its weight; 0 without the
    module), each summed over its rows and divided by ``n_seqs``."""
    with jax.default_matmul_precision("highest"):
        h = hidden(weights, tokens, positions, seg, arch, mode, block_rows)
        main = _xent_sum(weights, h, weights["lnf_g"], targets, valid, arch,
                         mode, head_rows) / n_seqs
        if "mtp" not in weights:
            return main, jnp.zeros(())
        t = h.shape[0]
        x = mtp_hidden(weights, h, targets, positions, seg, arch, mode,
                       block_rows)
        ok = valid & (jnp.roll(seg, -1) == seg) & (jnp.arange(t) < t - 1)
        mtp = _xent_sum(weights, x, weights["mtp"]["norm_g"],
                        jnp.roll(targets, -1), ok, arch, mode,
                        head_rows) / n_seqs
        return main, mtp


def loss(weights, tokens, positions, targets, seg, valid, n_seqs, **kw):
    main, mtp = losses(weights, tokens, positions, targets, seg, valid,
                       n_seqs, **kw)
    return main + kw["arch"]["mtp_weight"] * mtp


def adam_step(weights, m, v, grads, step, *, lr: float, b1: float,
              b2: float, eps: float):
    """Adam as published (bias-corrected, no decay).  ``step`` counts from
    0.  A leaf whose gradient is zero (the router's correction bias)
    stays as it is.  Returns (weights, m, v)."""
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
