"""A decoder whose every block runs a state-space branch BESIDE attention
behind :class:`ServingEngine`: the ``falcon_h1`` family's block (from one
normalised input a grouped-query attention branch with rotary positions
and a Mamba-2 state-space branch: input projection, causal depthwise
convolution, the selective recurrence, a gated grouped RMSNorm, output
projection; the two are added to the residual stream, then a SwiGLU; fixed
scalar multipliers sit on the embedding, the head, the key, the two
branches' inputs and outputs, five segments of the state-space projection
and the MLP; an untied head) as a
:class:`~paddle_tpu.serving.engine.DecodeModel`, one token a tick.

The layer equations are those of ``benchmarks/references/falcon_h1.py``.
The model's part of the contract beyond the required members:
:meth:`layer_state` (a slot's constant-size state in a layer: the
recurrence's ``ssm [heads, lanes, state]`` and the convolution's last
``taps - 1`` inputs ``conv [taps - 1, channels]``, float32; the engine
keeps them a slot in its one KV manager and hands them to :meth:`mix` and
back), :meth:`mix`, :meth:`rotate`, :meth:`attn_out_counted` and
``step_counters``.

:meth:`mix` keeps the rules of the engine's module doc ("recurrent
state") by the rows' layout alone: the slots' decode rows go one token a
slot through ``ops/ssd.ssd_step`` (an invalid row with ``dt = 0`` and
``xs = 0``, the identity), the bucket's chunks through ``ops/ssd.
ssd_chunks`` and ``ops/gated_delta.causal_conv(carry=)`` from the state
and carry their slots hold, a slot whose row stands at position 0 reading
zeros in their place.

Parameters are one flat ``{name: array}`` dictionary, float32, used as
they are handed over: no second copy of anything is made.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.gated_delta import causal_conv
from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.ops.rotary import rotary_lanes
from paddle_tpu.ops.ssd import CHUNK, ssd_chunks, ssd_step
from paddle_tpu.serving.engine import DecodeModel

__all__ = ["HybridSsmLM", "MULTIPLIERS"]

# the fixed scalars of a block (muP), by the published config's keys;
# ``ssm_multipliers`` scales the columns [z | x | B | C | dt] of the
# state-space projection, ``mlp_multipliers`` the gate and the output
MULTIPLIERS = {
    "embedding_multiplier": 1.0, "lm_head_multiplier": 1.0,
    "key_multiplier": 1.0, "attention_in_multiplier": 1.0,
    "attention_out_multiplier": 1.0, "ssm_in_multiplier": 1.0,
    "ssm_out_multiplier": 1.0, "ssm_multipliers": (1.0,) * 5,
    "mlp_multipliers": (1.0, 1.0)}


class HybridSsmLM(DecodeModel):
    # what ``attn_out_counted`` returns beside the rows, one int32 each,
    # summed by the engine over a step's layers: the state-space branch's
    # live decode rows and chunk rows, and the chunks that began a
    # sequence (position 0: from zeros) or continued one (from the state
    # the last chunk left)
    step_counters = ("ssm_rows_decode", "ssm_rows_prefill",
                     "ssm_segments_started", "ssm_segments_continued")

    def __init__(self, vocab_size: int, embed_dim: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 ffn_dim: int, ssm_heads: int, ssm_head_dim: int,
                 ssm_state: int, ssm_groups: int, conv_taps: int = 4,
                 chunk: int = CHUNK, rope_theta: float = 1e4,
                 norm_eps: float = 1e-5,
                 multipliers: Optional[Dict[str, object]] = None):
        if num_heads % num_kv_heads:
            raise ValueError(f"num_kv_heads ({num_kv_heads}) must divide "
                             f"num_heads ({num_heads})")
        if ssm_heads % ssm_groups:
            raise ValueError(f"ssm_groups ({ssm_groups}) must divide "
                             f"ssm_heads ({ssm_heads})")
        unknown = set(multipliers or ()) - set(MULTIPLIERS)
        if unknown:
            raise ValueError(f"unknown multipliers {sorted(unknown)}: one "
                             f"of {sorted(MULTIPLIERS)}")
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ffn_dim = ffn_dim
        self.ssm_heads = ssm_heads
        self.ssm_head_dim = ssm_head_dim
        self.ssm_state = ssm_state
        self.ssm_groups = ssm_groups
        self.conv_taps = conv_taps
        self.chunk = int(chunk)
        self.norm_eps = float(norm_eps)
        self.mult = {**MULTIPLIERS, **(multipliers or {})}
        self._inv_freq = (float(rope_theta) ** (
            -np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
        ).astype(np.float32)
        # the state-space projection's columns [z | x | B | C | dt] and
        # the multiplier of each
        d, gn = ssm_heads * ssm_head_dim, ssm_groups * ssm_state
        self._ssm_split = (d, d + gn + gn, ssm_heads)    # z | xBC | dt
        self._mu = np.repeat(
            np.asarray(self.mult["ssm_multipliers"], np.float32),
            (d, d, gn, gn, ssm_heads))

    # ---- the recurrent kind ---------------------------------------------

    def layer_state(self, layer: int):
        """What ONE slot keeps in ``layer`` whatever its sequence's
        length: ``{leaf: (shape, dtype)}``."""
        d, gn = (self.ssm_heads * self.ssm_head_dim,
                 self.ssm_groups * self.ssm_state)
        return {"ssm": ((self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                        jnp.float32),
                "conv": ((self.conv_taps - 1, d + 2 * gn), jnp.float32)}

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        e, v, f = self.embed_dim, self.vocab_size, self.ffn_dim
        q, kv = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        d, gn, hs = (self.ssm_heads * self.ssm_head_dim,
                     self.ssm_groups * self.ssm_state, self.ssm_heads)
        shapes = {"emb": (v, e), "out": (e, v), "norm": (e,)}
        for l in range(self.num_layers):
            pre = f"l{l}."
            shapes.update({
                pre + "ln1": (e,), pre + "wq": (e, q), pre + "wk": (e, kv),
                pre + "wv": (e, kv), pre + "wo": (q, e),
                pre + "ssm_in": (e, 2 * d + 2 * gn + hs),
                pre + "conv_w": (d + 2 * gn, self.conv_taps),
                pre + "conv_b": (d + 2 * gn,), pre + "dt_bias": (hs,),
                pre + "a_log": (hs,), pre + "d": (hs,),
                pre + "ssm_norm": (d,), pre + "ssm_out": (d, e),
                pre + "ln2": (e,), pre + "ffn_gate": (e, f),
                pre + "ffn_up": (e, f), pre + "ffn_down": (f, e)})
        return shapes

    def init_params(self, key) -> Dict[str, jax.Array]:
        p = {}
        for i, (name, shape) in enumerate(sorted(self.param_shapes().items())):
            k = jax.random.fold_in(key, i)
            r = jax.random.normal(k, shape, jnp.float32)
            if name.endswith((".ln1", ".ln2", "norm", ".d")):
                p[name] = 1.0 + 0.02 * r          # gains about 1
            elif name.endswith(".conv_b"):
                p[name] = 0.02 * r
            elif name.endswith(".conv_w"):
                p[name] = 0.5 * (1.0 + 0.02 * r)
            elif name.endswith(".a_log"):
                # A = -exp(a_log), uniform in -16..-1
                p[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                     1.0, 16.0))
            elif name.endswith(".dt_bias"):
                # softplus(dt_bias) log-uniform in 0.001..0.1
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                p[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                # matrices scaled to keep the rows' size
                p[name] = r * shape[-2] ** -0.5
        return p

    # ---- the attention branch -------------------------------------------

    def embed(self, params, tokens, positions):
        # positions are rotary's
        return params["emb"][tokens] * self.mult["embedding_multiplier"]

    def qkv(self, params, layer, x):
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        lead, pre = x.shape[:-1], f"l{layer}."
        u = rms_norm(x, params[pre + "ln1"], self.norm_eps) \
            * self.mult["attention_in_multiplier"]
        return ((u @ params[pre + "wq"]).reshape(lead + (h, d)),
                ((u @ params[pre + "wk"]) * self.mult["key_multiplier"]
                 ).reshape(lead + (kvh, d)),
                (u @ params[pre + "wv"]).reshape(lead + (kvh, d)))

    def rotate(self, params, layer, q, k, positions):
        """q ``[T, H, D]`` and k ``[T, H_kv, D]`` turned by their rows'
        positions: all lanes of a head, half-split pairing."""
        return (rotary_lanes(q, positions, self._inv_freq),
                rotary_lanes(k, positions, self._inv_freq))

    # ---- the state-space branch -----------------------------------------

    def mix(self, params, layer, x, state, rows):
        """The state-space branch of ``layer`` on the block input ``x [T,
        E]``: ``((the branch's rows [T, E], its counts), the layer's state
        arrays)``.  ``state``: ``{"ssm": [slots, H_s, P, N], "conv":
        [slots, K - 1, C]}``; ``rows``: the tick's layout (the
        ``DecodeModel`` contract)."""
        pre, eps = f"l{layer}.", self.norm_eps
        row_seq, pos, live = rows["row_seq"], rows["pos"], rows["live"]
        bd, t = int(rows["decode_rows"]), x.shape[0]
        hs, p, n, g = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                       self.ssm_groups)
        d, gn = hs * p, g * n
        ssm, conv = state["ssm"], state["conv"]
        slots = ssm.shape[0]
        if bd != slots:
            raise ValueError(f"{bd} decode rows for {slots} slots: the "
                             "state-space branch takes one row a slot")
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm.proj"):
                u = rms_norm(x, params[pre + "ln1"], eps) \
                    * self.mult["ssm_in_multiplier"]
                proj = (u @ params[pre + "ssm_in"]) * self._mu
                z, xbc, dt = jnp.split(
                    proj, np.cumsum(self._ssm_split)[:-1], axis=-1)
            # a slot whose row stands at position 0 begins a sequence: it
            # reads zeros whatever the slot held (no clearing pass on the
            # host; the selects fuse into the passes below)
            begins = live & (pos == 0)
            fresh = jnp.zeros((slots,), bool).at[
                jnp.where(begins, row_seq, slots)].set(True, mode="drop")
            ssm = jnp.where(fresh[:, None, None, None], 0.0, ssm)
            conv = jnp.where(fresh[:, None, None], 0.0, conv)
            d_live, p_live = live[:bd], live[bd:]
            w, b = params[pre + "conv_w"], params[pre + "conv_b"]
            with jax.named_scope("ssm.conv"):
                # decode rows: row s is slot s's, behind its carry
                win = jnp.concatenate([conv, xbc[:bd, None, :]], axis=1)
                act = [jnp.sum(win * w.T[None], axis=1) + b]
                conv = jnp.where(d_live[:, None, None], win[:, 1:], conv)
                if t > bd:
                    # the bucket's chunks, each behind its slot's carry
                    seg = jnp.where(p_live, row_seq[bd:], -1)
                    got, conv = causal_conv(xbc[bd:], w, seg, carry=conv,
                                            bias=b)
                    act.append(got)
                act = jax.nn.silu(jnp.concatenate(act))
                xs = jnp.where(live[:, None], act[:, :d], 0.0
                               ).reshape(t, hs, p)
                bm = act[:, d:d + gn].reshape(t, g, n)
                cm = act[:, d + gn:].reshape(t, g, n)
            with jax.named_scope("ssm.scan"):
                # (a row that is not live: dt = 0 and xs = 0, the identity)
                dt = jnp.where(live[:, None], jax.nn.softplus(
                    dt + params[pre + "dt_bias"]), 0.0)
                a = -jnp.exp(params[pre + "a_log"])
                y, ssm = ssd_step(xs[:bd], dt[:bd], a, bm[:bd], cm[:bd],
                                  params[pre + "d"], ssm)
                if t > bd:
                    # a chunk's padding rows stay its slot's (identity
                    # rows); behind the last live row nothing is walked
                    at = jnp.arange(t - bd)
                    end = jnp.max(jnp.where(p_live, at + 1, 0))
                    yp, ssm = ssd_chunks(
                        xs[bd:], dt[bd:], a, bm[bd:], cm[bd:],
                        params[pre + "d"],
                        jnp.where(at < end, row_seq[bd:], -1), ssm,
                        chunk=self.chunk)
                    y = jnp.concatenate([y, yp])
            with jax.named_scope("ssm.out"):
                # the gated norm, a group of heads at a time
                gated = (y.reshape(t, d) * jax.nn.silu(z)).reshape(t, g, -1)
                gated = gated * jax.lax.rsqrt(jnp.mean(
                    jnp.square(gated), axis=-1, keepdims=True) + eps)
                out = ((gated.reshape(t, d) * params[pre + "ssm_norm"])
                       @ params[pre + "ssm_out"]) \
                    * self.mult["ssm_out_multiplier"]
            first = jnp.concatenate([
                jnp.ones((min(1, t - bd),), bool),
                row_seq[bd + 1:] != row_seq[bd:-1]]) & p_live
            counts = jnp.stack([
                jnp.sum(d_live), jnp.sum(p_live),
                jnp.sum(first & (pos[bd:] == 0)),
                jnp.sum(first & (pos[bd:] > 0))]).astype(jnp.int32)
        return (out, counts), {"ssm": ssm, "conv": conv}

    # ---- behind the two branches ----------------------------------------

    def attn_out_counted(self, params, layer, ctx, x,
                         valid: Optional[jax.Array], mixed):
        """The attention branch's output projection, the sum of the two
        branches with the residual stream, the scaled SwiGLU: ``(x [T, E],
        the state-space branch's counts)``.  ``mixed`` is what
        :meth:`mix` made of the same block input."""
        pre = f"l{layer}."
        branch, counts = mixed
        with jax.named_scope("attn"):
            flat = ctx.reshape(x.shape[:-1]
                               + (self.num_heads * self.head_dim,))
            a = x + (flat @ params[pre + "wo"]) \
                * self.mult["attention_out_multiplier"] + branch
        with jax.named_scope("ffn"):
            hn = rms_norm(a, params[pre + "ln2"], self.norm_eps)
            m_gate, m_down = self.mult["mlp_multipliers"]
            y = (jax.nn.silu((hn @ params[pre + "ffn_gate"]) * m_gate)
                 * (hn @ params[pre + "ffn_up"])) @ params[pre + "ffn_down"]
            return a + y * m_down, counts

    def attn_out(self, params, layer, ctx, x, mixed):
        return self.attn_out_counted(params, layer, ctx, x, None, mixed)[0]

    def logits(self, params, x):
        with jax.named_scope("head"):
            return (rms_norm(x, params["norm"], self.norm_eps)
                    @ params["out"]) * self.mult["lm_head_multiplier"]
