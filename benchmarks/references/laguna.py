"""Plain reference for the ``laguna`` family (Laguna-XS.2: a decoder with
sliding-window layers beside full ones, query-head counts that differ by
layer, and a sigmoid-routed expert layer with a shared expert), in
straightforward ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no ring of pages, no sorting of rows by expert, no
batching: attention is a dense softmax over the whole token buffer under
the layer's mask, computed ``block_rows`` query rows and one KV head's
group of query heads at a time so the score matrix fits, and the expert
layer runs every held expert over every row and weights the chosen ones.

The model.  Block ``l`` on rows ``x [T, E]`` at positions ``p``, of kind
``full`` or ``window`` (``layer_windows[l]`` None or ``W``), with ``H_l =
layer_heads[l]`` query heads on ``H_kv`` KV heads of ``D`` lanes::

    h = rms_norm(x; ln1_g)
    q = h wq -> [T, H_l, D];  k = h wk, v = h wv -> [T, H_kv, D]  (no biases,
                                                     no per-head q/k norm)
    rotary, half-split pairing inside the lanes that turn:
      full layers:   the first D * partial lanes of a head, YaRN
                     frequencies, cos and sin times attention_factor
      window layers: all D lanes, theta ** (-2 i / D), no scaling
    query head i reads KV head i // (H_l / H_kv); scores / sqrt(D)
    a query at position p sees key j iff j <= p, and in a window layer
      iff also p - W < j
    ctx[:, i, :] *= sigmoid(h gate)[:, i]          gate [E, H_l]
    a = x + ctx wo
    h2 = rms_norm(a; ln2_g)
    dense layer:   out = a + (silu(h2 ffn_gate) * (h2 ffn_up)) ffn_down
    sparse layer:  s = sigmoid(h2 router) in float32       [T, N]
                   the top_k experts with the largest s + bias
                   w_e = scaling * s_e / (sum of the chosen s)
                   out = a + sum_{e chosen AND held} w_e FFN_e(h2)
                           + FFN_shared(h2)
                   FFN = (silu(. w_gate) * (. w_up)) w_down

and the head is ``rms_norm(x; norm_g) head``, untied.  Row ``p``'s logits
judge the token at position ``p + 1``.

The held share.  The router scores all ``N`` experts; ``held = (first,
count)`` says whose matrices ``w_gate``, ``w_up`` [count, E, F] and
``w_down`` [count, F, E] are: a chosen expert outside the share adds
nothing, the shared expert is computed whole.  ``held = (0, N)`` is the
uncut layer; ``shared=False`` leaves the shared expert out (so that the
shares of a layer can be added up with it counted once).

Weights are a canonical tree made by ``harness/weights.py``::

    {"wte": [V, E], "head": [E, V], "norm_g": [E], "blocks": [ {...} ] * L}
    block: ln1_g ln2_g [E], wq [E, H_l D], wk wv [E, H_kv D], wo [H_l D, E],
           gate [E, H_l], and ffn_gate ffn_up [E, F_d], ffn_down [F_d, E]
           or router [E, N], bias [N], w_gate w_up [count, E, F],
           w_down [count, F, E], shared_gate shared_up [E, F_s],
           shared_down [F_s, E]

``mode`` picks the arithmetic of every matrix product: ``f32`` is the
reference; ``bf16`` rounds both operands to bfloat16 (what the program
states); ``fp8`` rounds both to float8 e4m3 with one scale per row of the
left operand and per column of the right, the precision below the stated
one, used only as the control of ``correct``.  The router's product is
float32 in every mode, as the program states it.

:class:`RowLogits`.  At the cell's sizes (16,640 positions, 100,352
columns) the logits of every row are 6.7 GB beside 9 GB of weights, which
no chip holds, and the check reads the rows of the served tokens only.  So
:func:`logits` returns the final hidden rows with the head beside them,
and the head's product is made for the rows that are asked for
(``result[a:b]``: ``[b - a, V]``, computed like every other product of
``mode``).
"""

from __future__ import annotations

import math

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
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float, beta_slow: float):
    """The ``dim / 2`` inverse frequencies of the ``yarn`` rope type
    (arXiv 2309.00071, as the published transformer library computes
    them, its ``truncate`` left at true): pair ``i`` turns by ``theta **
    (-2 i / dim)`` a position where that is more than ``beta_fast`` turns
    in ``original_max`` positions, by that over ``factor`` where it is
    fewer than ``beta_slow``, and by a linear blend over ``i`` between."""

    def pair_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0).astype(np.float32)
    return (plain / np.float32(factor) * ramp + plain * (1 - ramp)
            ).astype(np.float32)


def frequencies(head_dim: int, rope: dict):
    """(inverse frequencies [lanes that turn / 2], factor on cos and sin)
    from one entry of the config's ``rope_parameters``."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "default":
        return (theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
                ).astype(np.float32), 1.0
    return yarn_frequencies(
        dim, theta, float(rope["factor"]),
        int(rope["original_max_position_embeddings"]),
        float(rope["beta_fast"]), float(rope["beta_slow"])), \
        float(rope["attention_factor"])


def rotary(x, positions, inv_freq, scale: float):
    """x [T, heads, D]; positions [T].  Of the first ``2 * len(inv_freq)``
    lanes, lane ``i`` turns with lane ``i + len(inv_freq)`` by ``position
    * inv_freq[i]`` (the ``rotate_half`` pairing inside the lanes that
    turn), cos and sin times ``scale``; the other lanes pass."""
    half = inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def attention(h, b, positions, *, n_head: int, n_kv_head: int, head_dim: int,
              window, inv_freq, scale: float, block_rows: int, mode: str):
    """The gated attention output ``[T, H_l D]`` of one block from its
    normalised input ``h [T, E]``: K and V for every row, then the rows'
    queries, scores and gate ``block_rows`` at a time, one KV head's group
    of query heads after the other."""
    t = h.shape[0]
    g = n_head // n_kv_head
    k = rotary(matmul(h, b["wk"], mode).reshape(t, n_kv_head, head_dim),
               positions, inv_freq, scale)
    v = matmul(h, b["wv"], mode).reshape(t, n_kv_head, head_dim)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [H_kv, T, D]
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    def one_block(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, block_rows)
        pb = jax.lax.dynamic_slice_in_dim(positions, start, block_rows)
        q = rotary(matmul(hb, b["wq"], mode).reshape(block_rows, n_head,
                                                     head_dim),
                   pb, inv_freq, scale)
        gate = jax.nn.sigmoid(matmul(hb, b["gate"], mode))   # [rows, H_l]
        ok = positions[None, :] <= pb[:, None]
        if window is not None:
            ok = ok & (positions[None, :] > pb[:, None] - window)
        # [H_kv, g * rows, D]: a KV head's query heads side by side
        qg = q.reshape(block_rows, n_kv_head, g, head_dim
                       ).transpose(1, 2, 0, 3).reshape(n_kv_head, -1,
                                                       head_dim)

        def one_kv_head(args):
            qh, kh, vh = args
            s = matmul(qh, kh.T, mode) * (head_dim ** -0.5)
            s = jnp.where(jnp.tile(ok, (g, 1)), s, -1e30)
            return matmul(jax.nn.softmax(s, axis=-1), vh, mode)

        ctx = jax.lax.map(one_kv_head, (qg, kt, vt))   # [H_kv, g rows, D]
        ctx = ctx.reshape(n_kv_head, g, block_rows, head_dim
                          ).transpose(2, 0, 1, 3).reshape(block_rows, n_head,
                                                          head_dim)
        return (ctx * gate[:, :, None]).reshape(block_rows,
                                                n_head * head_dim)

    out = jax.lax.map(one_block, jnp.arange(0, t, block_rows))
    return out.reshape(t, n_head * head_dim)


def swiglu(h, w_gate, w_up, w_down, mode: str):
    return matmul(jax.nn.silu(matmul(h, w_gate, mode))
                  * matmul(h, w_up, mode), w_down, mode)


def route(h, b, top_k: int, scaling: float):
    """(experts [R, top_k], weights [R, top_k]): sigmoid scores in
    float32, the ``top_k`` largest of score + bias, their weights the
    scores (without the bias) over their sum, times ``scaling``."""
    s = jax.nn.sigmoid(jnp.matmul(h, b["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + b["bias"], top_k)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, scaling * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(h, b, *, top_k: int, scaling: float, held, mode: str,
            shared: bool = True):
    """The expert layer on rows h [R, E]: every held expert run over every
    row, one expert at a time, weighted by what the row gave it (0 where
    it was not chosen); the shared expert added whole."""
    first, count = held
    chosen, top = route(h, b, top_k, scaling)
    n = b["router"].shape[-1]
    weight = jnp.sum(jax.nn.one_hot(chosen, n, dtype=jnp.float32)
                     * top[..., None], axis=1)                  # [R, N]
    weight = weight[:, first:first + count]

    def one(y, e):
        wg, wu, wd, w = e
        return y + w[:, None] * swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (b["w_gate"], b["w_up"], b["w_down"], weight.T))
    if shared:
        y = y + swiglu(h, b["shared_gate"], b["shared_up"], b["shared_down"],
                       mode)
    return y


def hidden(weights, tokens, positions, *, layer_heads, layer_windows,
           layer_sparse, n_kv_head: int, head_dim: int, top_k: int,
           scaling: float, held, rope_full: dict, rope_window: dict,
           eps: float, mode: str = "f32", block_rows: int = 256):
    """The residual stream after the last block, before the final norm:
    ``[T, E]``."""
    rope = {False: frequencies(head_dim, rope_full),
            True: frequencies(head_dim, rope_window)}
    x = weights["wte"][tokens]
    for l, b in enumerate(weights["blocks"]):
        window = layer_windows[l]
        inv_freq, scale = rope[window is not None]
        h = rms_norm(x, b["ln1_g"], eps)
        ctx = attention(h, b, positions, n_head=layer_heads[l],
                        n_kv_head=n_kv_head, head_dim=head_dim,
                        window=window, inv_freq=inv_freq, scale=scale,
                        block_rows=block_rows, mode=mode)
        a = x + matmul(ctx, b["wo"], mode)
        h2 = rms_norm(a, b["ln2_g"], eps)
        if layer_sparse[l]:
            x = a + experts(h2, b, top_k=top_k, scaling=scaling, held=held,
                            mode=mode)
        else:
            x = a + swiglu(h2, b["ffn_gate"], b["ffn_up"], b["ffn_down"],
                           mode)
    return x


@jax.tree_util.register_pytree_node_class
class RowLogits:
    """The logits of every row, the head's product left for the rows that
    are asked for: ``rows[a:b]`` is ``[b - a, V]`` (the module's doc)."""

    def __init__(self, final, head, mode: str):
        self.final, self.head, self.mode = final, head, mode

    def tree_flatten(self):
        return (self.final, self.head), self.mode

    @classmethod
    def tree_unflatten(cls, mode, children):
        return cls(*children, mode)

    @property
    def shape(self):
        return (self.final.shape[0], self.head.shape[1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return matmul(self.final[rows], self.head, self.mode)


def logits(weights, tokens, positions, *, eps: float, mode: str = "f32",
           block_rows: int = 256, **arch) -> RowLogits:
    """:class:`RowLogits` over the buffer: row ``p`` judges the token at
    position ``p + 1``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, positions, eps=eps, mode=mode,
                   block_rows=block_rows, **arch)
        return RowLogits(rms_norm(x, weights["norm_g"], eps),
                         weights["head"], mode)
