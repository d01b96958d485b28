"""A block-diffusion expert LM behind :class:`ServingEngine`: the
``sdar_moe`` family's decoder (weighted RMSNorm, rotary positions,
grouped-query attention with per-head q/k norms, a softmax-routed expert
layer in every block, an untied head) as a
:class:`~paddle_tpu.serving.engine.DecodeModel`.

The layer equations are those of ``benchmarks/references/sdar_moe.py``.
Generation is by diffusion over blocks: the engine reads
``block_length`` / ``denoise_steps`` / ``mask_token_id`` off the model,
computes a slot's open block of ``block_length`` rows a tick (and, in the
tick that commits a full block, that block's rows before them), lets a
row see its whole block, and fixes ``block_length / denoise_steps`` tokens
a denoising pass (``engine.py``: "block models").  The model's part of
that is three optional members of the contract: :meth:`rotate`
(positions reach q and k after ``qkv``), :meth:`attn_out_counted` (the
rows' validity reaches the expert layer, its counters reach
``ServingMetrics``) and ``step_counters`` (their names).

Parameters are one flat ``{name: array}`` dictionary, float32, used as
they are handed over: the experts' matrices are stacked ``[experts, ...]``
as ``parallel/moe.py moe_dropless`` takes them, and no second copy of
anything is made.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.ops.rotary import rotary
from paddle_tpu.parallel.moe import moe_dropless
from paddle_tpu.serving.engine import DecodeModel

__all__ = ["BlockMoeLM"]

# rows of a tile of the experts' grouped products: a tick brings an expert
# a few dozen rows at most, and a tile is computed whole
EXPERT_TILE_ROWS = 32


class BlockMoeLM(DecodeModel):
    # what ``attn_out_counted`` returns beside the rows, one int32 each,
    # summed by the engine over a step's layers
    step_counters = ("moe_rows_total", "moe_max_expert_rows",
                     "moe_live_experts", "moe_live_tiles", "moe_grid_tiles")

    def __init__(self, vocab_size: int, num_layers: int, embed_dim: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 num_experts: int, experts_per_token: int, expert_dim: int,
                 block_length: int, denoise_steps: int, mask_token_id: int,
                 rope_theta: float = 1e6, norm_eps: float = 1e-6):
        if num_heads % num_kv_heads:
            raise ValueError(f"num_kv_heads ({num_kv_heads}) must divide "
                             f"num_heads ({num_heads})")
        if block_length % denoise_steps:
            raise ValueError(f"denoise_steps ({denoise_steps}) must divide "
                             f"block_length ({block_length})")
        if not 0 <= mask_token_id < vocab_size:
            raise ValueError(f"mask_token_id ({mask_token_id}) is not a "
                             f"token of the vocabulary ({vocab_size})")
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.experts_per_token = experts_per_token
        self.expert_dim = expert_dim
        self.block_length = block_length
        self.denoise_steps = denoise_steps
        self.mask_token_id = mask_token_id
        self.rope_theta = float(rope_theta)
        self.norm_eps = float(norm_eps)

    def init_params(self, key) -> Dict[str, jax.Array]:
        e, v, d = self.embed_dim, self.vocab_size, self.head_dim
        q, kv = self.num_heads * d, self.num_kv_heads * d
        n, f = self.num_experts, self.expert_dim
        shapes = {"emb": (v, e), "out": (e, v), "norm": (e,)}
        for l in range(self.num_layers):
            shapes.update({
                f"l{l}.ln1": (e,), f"l{l}.wq": (e, q), f"l{l}.wk": (e, kv),
                f"l{l}.wv": (e, kv), f"l{l}.wo": (q, e), f"l{l}.q_norm": (d,),
                f"l{l}.k_norm": (d,), f"l{l}.ln2": (e,),
                f"l{l}.router": (e, n), f"l{l}.w_gate": (n, e, f),
                f"l{l}.w_up": (n, e, f), f"l{l}.w_down": (n, f, e)})
        p = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            r = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            # gains about 1, matrices scaled to keep the rows' size
            p[name] = 1.0 + 0.02 * r if len(shape) == 1 \
                else r * shape[-2] ** -0.5
        return p

    def embed(self, params, tokens, positions):
        return params["emb"][tokens]          # positions are rotary's

    def qkv(self, params, layer, x):
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        lead, pre = x.shape[:-1], f"l{layer}."
        xn = rms_norm(x, params[pre + "ln1"], self.norm_eps)
        q = (xn @ params[pre + "wq"]).reshape(lead + (h, d))
        k = (xn @ params[pre + "wk"]).reshape(lead + (kvh, d))
        v = (xn @ params[pre + "wv"]).reshape(lead + (kvh, d))
        return (rms_norm(q, params[pre + "q_norm"], self.norm_eps),
                rms_norm(k, params[pre + "k_norm"], self.norm_eps), v)

    def rotate(self, params, layer, q, k, positions):
        """q ``[T, H, D]`` and k ``[T, H_kv, D]`` turned by their rows'
        positions, all lanes, half-split pairing."""
        return (rotary(q, positions, self.rope_theta),
                rotary(k, positions, self.rope_theta))

    def attn_out_counted(self, params, layer, ctx, x,
                         valid: Optional[jax.Array]):
        """``attn_out`` with the rows' validity (``[T]`` bool; padding
        rows take no expert) and the expert layer's counters beside the
        rows: ``(x [T, E], int32 [len(step_counters)])``."""
        pre = f"l{layer}."
        flat = ctx.reshape(x.shape[:-1] + (self.num_heads * self.head_dim,))
        with jax.named_scope("attn"):
            a = x + flat @ params[pre + "wo"]
        with jax.named_scope("ffn"):
            h = rms_norm(a, params[pre + "ln2"], self.norm_eps)
            rows = h.reshape(-1, self.embed_dim)
            y, stats = moe_dropless(
                rows, {k: params[pre + k] for k in
                       ("router", "w_gate", "w_up", "w_down")},
                top_k=self.experts_per_token, held=(0, self.num_experts),
                routing="softmax",
                valid=None if valid is None else valid.reshape(-1),
                tile_m=EXPERT_TILE_ROWS, operand_dtype=jnp.float32)
        # the grid's row tiles (``grouped_matmul.padded_rows``)
        grid = -(-rows.shape[0] * self.experts_per_token
                 // EXPERT_TILE_ROWS) + self.num_experts
        counts = jnp.stack([stats["rows_total"], stats["max_expert_rows"],
                            stats["live_experts"], stats["live_tiles"],
                            jnp.asarray(grid, jnp.float32)])
        return a + y.reshape(a.shape), counts.astype(jnp.int32)

    def attn_out(self, params, layer, ctx, x):
        return self.attn_out_counted(params, layer, ctx, x, None)[0]

    def logits(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["norm"], self.norm_eps) @ params["out"]
