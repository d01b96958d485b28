"""A decoder whose whole stack runs several times over the SAME weights
behind :class:`ServingEngine`: the ``ouro`` family's looped language model
(a plain multi-head decoder with rotary positions, RMSNorm before AND
after each sub-layer, a SwiGLU, an untied head; the stack of
``num_layers`` layers is applied ``loops`` times, the final norm closes
every pass and its output is the next pass's input, a learned gate reads
each pass's output) as a :class:`~paddle_tpu.serving.engine.DecodeModel`,
one token a tick.

The layer equations are those of ``benchmarks/references/ouro.py``.  The
model's part of the contract beyond the required members: ``loops`` and
:meth:`close_pass` (the engine's module doc, "looped models": weight
layer ``l`` at pass ``t`` keeps cache layer ``t * num_layers + l``, so a
pass's keys and values are read by that pass alone), and :meth:`rotate`.

Parameters are one flat ``{name: array}`` dictionary, float32, used as
they are handed over: a pass indexes the same arrays as the pass before
it, nothing is stacked or tiled ``loops`` times, no second copy of
anything is made.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.ops.rotary import rotary_lanes
from paddle_tpu.serving.engine import DecodeModel

__all__ = ["LoopedLM"]


class LoopedLM(DecodeModel):
    def __init__(self, vocab_size: int, embed_dim: int, num_layers: int,
                 num_heads: int, head_dim: int, ffn_dim: int, loops: int,
                 rope_theta: float = 1e6, norm_eps: float = 1e-6):
        if loops < 1:
            raise ValueError(f"loops must be at least 1, got {loops}")
        self.vocab_size = vocab_size
        self.embed_dim = embed_dim
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_heads       # multi-head attention
        self.head_dim = head_dim
        self.ffn_dim = ffn_dim
        self.loops = int(loops)
        self.norm_eps = float(norm_eps)
        self._inv_freq = (float(rope_theta) ** (
            -np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
        ).astype(np.float32)

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        e, v, f = self.embed_dim, self.vocab_size, self.ffn_dim
        q = self.num_heads * self.head_dim
        shapes = {"emb": (v, e), "out": (e, v), "norm": (e,),
                  "gate_w": (e, 1), "gate_b": (1,)}
        for l in range(self.num_layers):
            pre = f"l{l}."
            shapes.update({
                pre + "ln1": (e,), pre + "ln2": (e,), pre + "ln3": (e,),
                pre + "ln4": (e,), pre + "wq": (e, q), pre + "wk": (e, q),
                pre + "wv": (e, q), pre + "wo": (q, e),
                pre + "ffn_gate": (e, f), pre + "ffn_up": (e, f),
                pre + "ffn_down": (f, e)})
        return shapes

    def init_params(self, key) -> Dict[str, jax.Array]:
        p = {}
        for i, (name, shape) in enumerate(sorted(self.param_shapes().items())):
            r = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name == "gate_b":
                p[name] = 0.02 * r
            elif len(shape) == 1:
                p[name] = 1.0 + 0.02 * r          # gains about 1
            else:
                # matrices scaled to keep the rows' size
                p[name] = r * shape[-2] ** -0.5
        return p

    # ---- a layer: attention ----------------------------------------------

    def embed(self, params, tokens, positions):
        # positions are rotary's
        return params["emb"][tokens]

    def qkv(self, params, layer, x):
        h, d = self.num_heads, self.head_dim
        lead, pre = x.shape[:-1], f"l{layer}."
        with jax.named_scope("proj"):
            u = rms_norm(x, params[pre + "ln1"], self.norm_eps)
            return ((u @ params[pre + "wq"]).reshape(lead + (h, d)),
                    (u @ params[pre + "wk"]).reshape(lead + (h, d)),
                    (u @ params[pre + "wv"]).reshape(lead + (h, d)))

    def rotate(self, params, layer, q, k, positions):
        """q and k ``[T, H, D]`` turned by their rows' positions: all
        lanes of a head, half-split pairing."""
        return (rotary_lanes(q, positions, self._inv_freq),
                rotary_lanes(k, positions, self._inv_freq))

    # ---- behind attention --------------------------------------------------

    def attn_out(self, params, layer, ctx, x):
        """The output projection under its own norm, the residual, and the
        SwiGLU between its two norms."""
        pre, eps = f"l{layer}.", self.norm_eps
        with jax.named_scope("attn"), jax.named_scope("proj"):
            flat = ctx.reshape(x.shape[:-1]
                               + (self.num_heads * self.head_dim,))
            a = x + rms_norm(flat @ params[pre + "wo"], params[pre + "ln2"],
                             eps)
        with jax.named_scope("ffn"):
            u = rms_norm(a, params[pre + "ln3"], eps)
            y = (jax.nn.silu(u @ params[pre + "ffn_gate"])
                 * (u @ params[pre + "ffn_up"])) @ params[pre + "ffn_down"]
            return a + rms_norm(y, params[pre + "ln4"], eps)

    # ---- between two passes, and behind the last ---------------------------

    def close_pass(self, params, t, x):
        """The final norm closes EVERY pass (its rows go into the next
        pass, the last pass's to :meth:`logits`), and the exit gate reads
        them: ``(rows [T, E], lam [T])``, ``lam`` the probability with
        which a row would leave behind this pass."""
        h = rms_norm(x, params["norm"], self.norm_eps)
        lam = jax.nn.sigmoid((h @ params["gate_w"])[..., 0]
                             + params["gate_b"][0])
        return h, lam

    def logits(self, params, x):
        # (the rows are the last pass's, already under the final norm)
        with jax.named_scope("head"):
            return x @ params["out"]
