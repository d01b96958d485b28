"""Plain reference for the ``falcon_h1`` family (Falcon-H1: a decoder whose
every block runs a Mamba-2 state-space branch beside grouped-query
attention), in straightforward ``jax.numpy`` at float32 with
``default_matmul_precision("highest")``.

It imports nothing of the program and takes nothing the program made.  No
kernels, no cache, no chunking, no state handed between calls: attention
is a dense softmax over the whole token buffer under the causal mask,
computed ``block_rows`` query rows and one KV head's group of query heads
at a time so the score matrix fits, and the state-space recurrence runs
TOKEN BY TOKEN (one ``lax.scan`` over the buffer from a zero state, the
convolution over a buffer padded with zero rows).

The model.  Block ``l`` on rows ``x [T, E]`` at positions ``p`` (eps the
same everywhere)::

    x0      = wte[token] * embedding_multiplier
    u       = rms_norm(x; ln1_g)
    # attention: H query heads on H_kv KV heads of D lanes, rotary on all
    # lanes (half-split pairing, theta ** (-2 i / D), no scaling)
    q, k, v = (u * attention_in_multiplier) (wq, wk, wv);  k *= key_multiplier
    a       = causal_softmax(q k^T / sqrt(D)) v, query head h on KV head
              h // (H / H_kv);   a = (a wo) * attention_out_multiplier
    # state-space: H_s heads of P lanes, state N a lane, G groups of B / C
    p       = ((u * ssm_in_multiplier) in_proj) * mu
              mu: ssm_multipliers[0..4] over the columns
              [z H_s P | x H_s P | B G N | C G N | dt H_s]
    xBC     = silu(causal_conv(x | B | C; conv_w [C, K]) + conv_b)
              depthwise, the last tap on the current row, a row before
              the sequence's start counting as zero
    dt      = softplus(dt + dt_bias);   A = -exp(a_log)
    H_t     = exp(dt_t A_h) H_{t-1} + dt_t outer(xs_t[h], B_t[h // (H_s / G)])
              H in R^{P x N}, zero before the first row
    y_t[h]  = H_t C_t[h // (H_s / G)] + d_h xs_t[h]
    y       = group_rms_norm(y * silu(z); ssm_norm_g)    G groups of lanes,
              the gate BEFORE the norm
    s       = (y out_proj) * ssm_out_multiplier
    x       = x + a + s
    h       = rms_norm(x; ln2_g)
    x       = x + ((silu((h ffn_gate) * mlp_multipliers[0]) * (h ffn_up))
                   ffn_down) * mlp_multipliers[1]

and the head is ``(rms_norm(x; norm_g) head) * lm_head_multiplier``,
untied.  Row ``p``'s logits judge the token at position ``p + 1``.  No
bias but the convolution's.

Weights are a canonical tree made by ``harness/weights.py``::

    {"wte": [V, E], "head": [E, V], "norm_g": [E], "blocks": [ {...} ] * L}
    block: ln1_g ln2_g [E], wq [E, H D], wk wv [E, H_kv D], wo [H D, E],
           in_proj [E, 2 H_s P + 2 G N + H_s], conv_w [H_s P + 2 G N, K],
           conv_b [H_s P + 2 G N], dt_bias a_log d [H_s], ssm_norm_g [H_s P],
           out_proj [H_s P, E], ffn_gate ffn_up [E, F], ffn_down [F, E]

``mode`` picks the arithmetic of every matrix product (the projections,
attention's two products, the MLP, the head): ``f32`` is the reference;
``bf16`` rounds both operands to bfloat16 (what the program states);
``fp8`` rounds both to float8 e4m3 with one scale per row of the left
operand and per column of the right, the precision below the stated one,
used only as the control of ``correct``.  The convolution, ``dt``, the
decays and the recurrence are float32 in every mode, as the program
states them.

:class:`RowLogits`.  The check reads the rows of the served tokens only,
so :func:`logits` returns the final hidden rows with the head beside
them, and the head's product is made for the rows that are asked for
(``result[a:b]``: ``[b - a, V]``, computed like every other product of
``mode``).
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
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


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


def attention(u, b, positions, *, n_head: int, n_kv_head: int, head_dim: int,
              theta: float, key_multiplier: float, block_rows: int,
              mode: str):
    """The attention context ``[T, H D]`` of one block from its scaled
    normalised input ``u [T, E]``: K and V for every row, then the rows'
    queries and scores ``block_rows`` at a time, one KV head's group of
    query heads after the other."""
    t = u.shape[0]
    g = n_head // n_kv_head
    k = rotary((matmul(u, b["wk"], mode) * key_multiplier
                ).reshape(t, n_kv_head, head_dim), positions, theta)
    v = matmul(u, b["wv"], mode).reshape(t, n_kv_head, head_dim)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [H_kv, T, D]
    block_rows = min(block_rows, t)
    assert t % block_rows == 0, (t, block_rows)

    def one_block(start):
        ub = jax.lax.dynamic_slice_in_dim(u, start, block_rows)
        pb = jax.lax.dynamic_slice_in_dim(positions, start, block_rows)
        q = rotary(matmul(ub, b["wq"], mode).reshape(block_rows, n_head,
                                                     head_dim), pb, theta)
        ok = positions[None, :] <= pb[:, None]
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
        return ctx.reshape(n_kv_head, g, block_rows, head_dim
                           ).transpose(2, 0, 1, 3).reshape(
                               block_rows, n_head * head_dim)

    out = jax.lax.map(one_block, jnp.arange(0, t, block_rows))
    return out.reshape(t, n_head * head_dim)


def causal_conv(x, w, bias):
    """Depthwise over one sequence: ``y_t = sum_j w[:, j] x_{t - (K - 1) +
    j} + bias``, rows before the first counting as zero.  x [T, C]; w [C,
    K]."""
    t, taps = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(taps)) + bias


def recurrence(xs, dt, a, bm, cm, d):
    """The definition, token by token from a zero state.  xs [T, H_s, P];
    dt [T, H_s]; a, d [H_s]; bm, cm [T, G, N].  Returns y [T, H_s, P]."""
    hs, p = xs.shape[1], xs.shape[2]
    rep = hs // bm.shape[1]

    def one(state, row):
        x_t, dt_t, b_t, c_t = row
        b_t, c_t = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) \
            + d[:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((hs, p, bm.shape[2]), jnp.float32),
                        (xs, dt, bm, cm))
    return y


def state_space(u, z_b, *, ssm_heads: int, ssm_head_dim: int,
                ssm_state: int, ssm_groups: int, ssm_multipliers,
                eps: float, mode: str):
    """The state-space branch's rows ``[T, H_s P]`` before the output
    projection, from the branch's scaled normalised input ``u [T, E]`` and
    the block's leaves ``z_b``."""
    t = u.shape[0]
    hs, p, n, g = ssm_heads, ssm_head_dim, ssm_state, ssm_groups
    d, gn = hs * p, g * n
    mu = np.repeat(np.asarray(ssm_multipliers, np.float32),
                   (d, d, gn, gn, hs))
    proj = matmul(u, z_b["in_proj"], mode) * mu
    z, xbc, dt = proj[:, :d], proj[:, d:2 * d + 2 * gn], proj[:, 2 * d + 2 * gn:]
    xbc = jax.nn.silu(causal_conv(xbc, z_b["conv_w"], z_b["conv_b"]))
    xs = xbc[:, :d].reshape(t, hs, p)
    bm = xbc[:, d:d + gn].reshape(t, g, n)
    cm = xbc[:, d + gn:].reshape(t, g, n)
    y = recurrence(xs, jax.nn.softplus(dt + z_b["dt_bias"]),
                   -jnp.exp(z_b["a_log"]), bm, cm, z_b["d"])
    gated = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, g, d // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return gated.reshape(t, d) * z_b["ssm_norm_g"]


def hidden(weights, tokens, positions, *, n_head: int, n_kv_head: int,
           head_dim: int, theta: float, ssm_heads: int, ssm_head_dim: int,
           ssm_state: int, ssm_groups: int, multipliers: dict, eps: float,
           mode: str = "f32", block_rows: int = 256):
    """The residual stream after the last block, before the final norm:
    ``[T, E]``."""
    m = multipliers
    x = weights["wte"][tokens] * m["embedding_multiplier"]
    for b in weights["blocks"]:
        u = rms_norm(x, b["ln1_g"], eps)
        ctx = attention(u * m["attention_in_multiplier"], b, positions,
                        n_head=n_head, n_kv_head=n_kv_head,
                        head_dim=head_dim, theta=theta,
                        key_multiplier=m["key_multiplier"],
                        block_rows=block_rows, mode=mode)
        a = matmul(ctx, b["wo"], mode) * m["attention_out_multiplier"]
        y = state_space(u * m["ssm_in_multiplier"], b, ssm_heads=ssm_heads,
                        ssm_head_dim=ssm_head_dim, ssm_state=ssm_state,
                        ssm_groups=ssm_groups,
                        ssm_multipliers=m["ssm_multipliers"], eps=eps,
                        mode=mode)
        s = matmul(y, b["out_proj"], mode) * m["ssm_out_multiplier"]
        x = x + a + s
        h = rms_norm(x, b["ln2_g"], eps)
        gate = jax.nn.silu(matmul(h, b["ffn_gate"], mode)
                           * m["mlp_multipliers"][0])
        x = x + matmul(gate * matmul(h, b["ffn_up"], mode), b["ffn_down"],
                       mode) * m["mlp_multipliers"][1]
    return x


@jax.tree_util.register_pytree_node_class
class RowLogits:
    """The logits of every row, the head's product left for the rows that
    are asked for: ``rows[a:b]`` is ``[b - a, V]`` (the module's doc)."""

    def __init__(self, final, head, mode: str, scale: float):
        self.final, self.head, self.mode, self.scale = final, head, mode, \
            scale

    def tree_flatten(self):
        return (self.final, self.head), (self.mode, self.scale)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def shape(self):
        return (self.final.shape[0], self.head.shape[1])

    def __getitem__(self, rows):
        with jax.default_matmul_precision("highest"):
            return matmul(self.final[rows], self.head, self.mode) * self.scale


def logits(weights, tokens, positions, *, eps: float, multipliers: dict,
           mode: str = "f32", block_rows: int = 256, **arch) -> RowLogits:
    """:class:`RowLogits` over the buffer: row ``p`` judges the token at
    position ``p + 1``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, positions, eps=eps,
                   multipliers=multipliers, mode=mode, block_rows=block_rows,
                   **arch)
        return RowLogits(rms_norm(x, weights["norm_g"], eps),
                         weights["head"], mode,
                         float(multipliers["lm_head_multiplier"]))
