"""Plain reference of the tests' second family (``families/gated_family.py``):
forward pass, loss, gradient and Adam in straightforward ``jax.numpy`` at
float32.  The arithmetic every family shares (a matrix product in a
``mode``, dense attention inside documents, the weighted norm, tanh GELU,
Adam) is the GPT-2 family's reference's own, taken from that file.

Tree: ``emb`` [V, D], ``head`` [D, V], ``final_g`` ``final_b`` [D],
``mix``: {``w`` [D, D, D], ``norm_g``, ``norm_b``}, ``blocks``: a list of
{``wq wk wv wo`` [D, D], ``norm1_g norm1_b norm2_g norm2_b`` [D], ``ffn``:
{``up gate`` [D, F], ``down`` [F, D], ``down_b`` [D]}}.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from harness import cells

base = cells.load_module(os.path.join(cells.ROOT, "references",
                                      "gpt2_family.py"))


def hidden(weights, tokens, seg, *, n_head: int, mode: str, block_rows: int):
    """The residual stream before the final norm: [T, D]."""
    norm = functools.partial(base._norm, kind="layernorm")
    x = weights["emb"][tokens]
    for b in weights["blocks"]:
        a = norm(x, b["norm1_g"], b["norm1_b"])
        q, k, v = (base.matmul(a, b[w], mode) for w in ("wq", "wk", "wv"))
        ctx = base._attention(q, k, v, seg, n_head, block_rows, mode)
        x = x + base.matmul(ctx, b["wo"], mode)
        h, ffn = norm(x, b["norm2_g"], b["norm2_b"]), b["ffn"]
        h = base._gelu_tanh(base.matmul(h, ffn["up"], mode)) * \
            base.matmul(h, ffn["gate"], mode)
        x = x + base.matmul(h, ffn["down"], mode) + ffn["down_b"]
    mix = weights["mix"]
    m = norm(x, mix["norm_g"], mix["norm_b"])
    # out[t, k] = m[t] . W[k] . m[t], as two products so that `mode` rounds
    half = base.matmul(m, mix["w"].transpose(1, 0, 2).reshape(m.shape[1], -1),
                       mode).reshape(m.shape[0], -1, m.shape[1])
    return x + jnp.sum(half * m[:, None, :], axis=-1)


def forward_logits(weights, tokens, seg, *, n_head: int, mode: str = "f32",
                   block_rows: int = 512):
    """Next-token logits at every row: [T, V]."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, seg, n_head=n_head, mode=mode,
                   block_rows=block_rows)
        x = base._norm(x, weights["final_g"], weights["final_b"], "layernorm")
        return base.matmul(x, weights["head"], mode)


def loss(weights, tokens, targets, seg, valid, n_seqs, **arch):
    """Summed next-token cross-entropy over the valid rows, divided by the
    number of documents (the trainer's cost)."""
    lg = forward_logits(weights, tokens, seg, **arch)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, jax.nn.logsumexp(lg, axis=-1) - picked,
                             0.0)) / n_seqs


def make_train_step(*, n_head: int, mode: str, lr: float, b1: float,
                    b2: float, eps: float, reduce_grads, block_rows: int,
                    frozen=()):
    """One jitted reference step, in the signature the train driver calls:
    (weights, m, v, step, key, tokens, positions, targets, seg, valid,
    n_seqs) -> (loss, reduce_grads(gradient tree, key), weights, m, v).
    A leaf whose flat name (``blocks.0.ffn.down_b``) is in ``frozen`` gets
    no update: Adam sees a zero gradient for it, and so moves it by
    nothing."""
    loss_fn = functools.partial(loss, n_head=n_head, mode=mode,
                                block_rows=block_rows)

    @jax.jit
    def step_fn(weights, m, v, step, key, tokens, positions, targets, seg,
                valid, n_seqs):
        value, grads = jax.value_and_grad(loss_fn)(
            weights, tokens, targets, seg, valid, n_seqs)
        reduced = reduce_grads(grads, key)
        held = jax.tree_util.tree_map_with_path(
            lambda path, g: jnp.zeros_like(g) if ".".join(
                str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            in frozen else g, grads)
        weights, m, v = base.adam_step(weights, m, v, held, step, lr=lr,
                                       b1=b1, b2=b2, eps=eps)
        return value, reduced, weights, m, v

    return step_fn
