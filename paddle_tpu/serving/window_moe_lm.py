"""An expert LM with window layers beside full ones behind
:class:`ServingEngine`: the ``laguna`` family's decoder (weighted RMSNorm,
grouped-query attention whose query-head count differs by layer, rotary
positions with YaRN frequencies on part of a head's lanes in the full
layers and plain ones on all lanes in the window layers, a per-head
sigmoid gate on the attention output, a dense SwiGLU first layer, then a
sigmoid-routed expert layer with a shared expert, an untied head) as a
:class:`~paddle_tpu.serving.engine.DecodeModel`, one token a tick.

The layer equations are those of ``benchmarks/references/laguna.py``.
The model's part of the contract beyond the required members:
:meth:`layer_window` (a layer's window, None for a full layer: the engine
keeps a ring of pages a slot for the window layers and the kernel skips
what lies below the window), :meth:`rotate`, :meth:`attn_out_counted` and
``step_counters``.  A layer's query heads are its ``q``'s: ``num_heads``
is the most any layer has.

The expert layer is ONE RANK'S SHARE of an expert-parallel group
(``parallel/moe.py moe_dropless``): the router scores all ``num_experts``
experts, this model holds ``held = (first, count)`` of them (their
matrices are stacked ``[count, ...]``) and adds their part of the result;
the shared expert is computed here whole.  Parameters are one flat
``{name: array}`` dictionary, float32, used as they are handed over: no
second copy of anything is made.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.norm import rms_norm
from paddle_tpu.ops.rotary import rotary_lanes, yarn_inv_freq
from paddle_tpu.parallel.moe import moe_dropless
from paddle_tpu.serving.engine import DecodeModel

__all__ = ["WindowMoeLM", "rope_frequencies"]

# rows of a tile of the experts' grouped products: a decode tick brings a
# held expert a row or two, a prefill chunk a few dozen; a tile is
# computed whole
EXPERT_TILE_ROWS = 32


def rope_frequencies(head_dim: int, rope: dict) -> Tuple[np.ndarray, float]:
    """(inverse frequencies, the factor on cos and sin) of one kind of
    layer from its rope parameters as a published config gives them:
    ``rope_theta``, ``partial_rotary_factor`` (the share of a head's lanes
    that turn, from the first), ``rope_type`` ``default`` or ``yarn`` with
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``, ``attention_factor``."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    kind = rope.get("rope_type", "default")
    if kind == "default":
        inv = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
        return inv.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not built: default or yarn")
    factor = float(rope["factor"])
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 1.0 if factor <= 1 else 0.1 * float(np.log(factor)) + 1.0
    return yarn_inv_freq(
        dim, theta, factor, int(rope["original_max_position_embeddings"]),
        float(rope.get("beta_fast") or 32), float(rope.get("beta_slow") or 1),
        bool(rope.get("truncate", True))), float(scale)


class WindowMoeLM(DecodeModel):
    # what ``attn_out_counted`` returns beside the rows, one int32 each,
    # summed by the engine over a step's layers (a dense layer adds zeros)
    step_counters = ("moe_rows_total", "moe_rows_held", "moe_max_expert_rows",
                     "moe_live_experts", "moe_live_tiles", "moe_grid_tiles")

    def __init__(self, vocab_size: int, embed_dim: int,
                 layer_heads: Sequence[int],
                 layer_windows: Sequence[Optional[int]],
                 layer_sparse: Sequence[bool], num_kv_heads: int,
                 head_dim: int, dense_dim: int, num_experts: int,
                 held: Tuple[int, int], experts_per_token: int,
                 expert_dim: int, shared_dim: int, routed_scaling: float,
                 rope_full: dict, rope_window: dict, norm_eps: float = 1e-6):
        n = len(layer_heads)
        if not (len(layer_windows) == len(layer_sparse) == n and n >= 1):
            raise ValueError("layer_heads, layer_windows and layer_sparse "
                             "give one entry a layer")
        for h in layer_heads:
            if h % num_kv_heads:
                raise ValueError(f"num_kv_heads ({num_kv_heads}) must divide "
                                 f"a layer's query heads ({h})")
        first, count = held
        if not (0 <= first and count >= experts_per_token >= 1
                and first + count <= num_experts):
            raise ValueError(f"held {held} is no share of {num_experts} "
                             f"experts at {experts_per_token} a token")
        self.vocab_size = vocab_size
        self.num_layers = n
        self.embed_dim = embed_dim
        self.layer_heads = tuple(int(h) for h in layer_heads)
        self.layer_windows = tuple(None if w is None else int(w)
                                   for w in layer_windows)
        self.layer_sparse = tuple(bool(b) for b in layer_sparse)
        self.num_heads = max(self.layer_heads)
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dense_dim = dense_dim
        self.num_experts = num_experts
        self.held = (int(first), int(count))
        self.experts_per_token = experts_per_token
        self.expert_dim = expert_dim
        self.shared_dim = shared_dim
        self.routed_scaling = float(routed_scaling)
        self.norm_eps = float(norm_eps)
        # a layer's rotary frequencies by its kind
        self._rope = {False: rope_frequencies(head_dim, rope_full),
                      True: rope_frequencies(head_dim, rope_window)}

    def layer_window(self, layer: int) -> Optional[int]:
        """How many of the most recent tokens the layer attends over
        (key ``j`` is live for query ``i`` iff ``i - window < j <= i``);
        None for a full layer."""
        return self.layer_windows[layer]

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        e, v, d = self.embed_dim, self.vocab_size, self.head_dim
        kv = self.num_kv_heads * d
        shapes = {"emb": (v, e), "out": (e, v), "norm": (e,)}
        for l, h in enumerate(self.layer_heads):
            pre = f"l{l}."
            shapes.update({
                pre + "ln1": (e,), pre + "wq": (e, h * d), pre + "wk": (e, kv),
                pre + "wv": (e, kv), pre + "wo": (h * d, e),
                pre + "wgate": (e, h), pre + "ln2": (e,)})
            if not self.layer_sparse[l]:
                f = self.dense_dim
                shapes.update({pre + "ffn_gate": (e, f),
                               pre + "ffn_up": (e, f),
                               pre + "ffn_down": (f, e)})
                continue
            n, f, s = self.held[1], self.expert_dim, self.shared_dim
            shapes.update({
                pre + "router": (e, self.num_experts),
                pre + "bias": (self.num_experts,),
                pre + "w_gate": (n, e, f), pre + "w_up": (n, e, f),
                pre + "w_down": (n, f, e), pre + "shared_gate": (e, s),
                pre + "shared_up": (e, s), pre + "shared_down": (s, e)})
        return shapes

    def init_params(self, key) -> Dict[str, jax.Array]:
        p = {}
        for i, (name, shape) in enumerate(sorted(self.param_shapes().items())):
            r = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith((".ln1", ".ln2", "norm")):
                p[name] = 1.0 + 0.02 * r          # gains about 1
            elif name.endswith(".bias"):
                p[name] = 0.02 * r                # the correction bias
            else:
                # matrices scaled to keep the rows' size
                p[name] = r * shape[-2] ** -0.5
        return p

    def embed(self, params, tokens, positions):
        return params["emb"][tokens]          # positions are rotary's

    def qkv(self, params, layer, x):
        h, kvh, d = self.layer_heads[layer], self.num_kv_heads, self.head_dim
        lead, pre = x.shape[:-1], f"l{layer}."
        xn = rms_norm(x, params[pre + "ln1"], self.norm_eps)
        return ((xn @ params[pre + "wq"]).reshape(lead + (h, d)),
                (xn @ params[pre + "wk"]).reshape(lead + (kvh, d)),
                (xn @ params[pre + "wv"]).reshape(lead + (kvh, d)))

    def rotate(self, params, layer, q, k, positions):
        """q ``[T, H_l, D]`` and k ``[T, H_kv, D]`` turned by their rows'
        positions with the layer's kind's frequencies: the first lanes of
        a head only where the kind's rotary is partial, half-split
        pairing inside them."""
        inv, scale = self._rope[self.layer_windows[layer] is not None]
        return (rotary_lanes(q, positions, inv, scale),
                rotary_lanes(k, positions, inv, scale))

    def attn_out_counted(self, params, layer, ctx, x,
                         valid: Optional[jax.Array]):
        """``attn_out`` with the rows' validity (``[T]`` bool; padding
        rows take no expert) and the expert layer's counters beside the
        rows: ``(x [T, E], int32 [len(step_counters)])``."""
        pre, h = f"l{layer}.", self.layer_heads[layer]
        with jax.named_scope("attn"):
            # the output gate: one scalar a head from the block's
            # normalised input
            xn = rms_norm(x, params[pre + "ln1"], self.norm_eps)
            gate = jax.nn.sigmoid(xn @ params[pre + "wgate"])
            flat = (ctx * gate[..., None].astype(ctx.dtype)).reshape(
                x.shape[:-1] + (h * self.head_dim,))
            a = x + flat @ params[pre + "wo"]
        with jax.named_scope("ffn"):
            hn = rms_norm(a, params[pre + "ln2"], self.norm_eps)
            if not self.layer_sparse[layer]:
                with jax.named_scope("ffn.dense"):
                    y = (jax.nn.silu(hn @ params[pre + "ffn_gate"])
                         * (hn @ params[pre + "ffn_up"])
                         ) @ params[pre + "ffn_down"]
                return a + y, jnp.zeros((len(self.step_counters),),
                                        jnp.int32)
            rows = hn.reshape(-1, self.embed_dim)
            y, stats = moe_dropless(
                rows, {k: params[pre + k] for k in
                       ("router", "bias", "w_gate", "w_up", "w_down",
                        "shared_gate", "shared_up", "shared_down")},
                top_k=self.experts_per_token, held=self.held,
                routing="sigmoid", scaling=self.routed_scaling,
                valid=None if valid is None else valid.reshape(-1),
                tile_m=EXPERT_TILE_ROWS, operand_dtype=jnp.float32)
        # the grid's row tiles (``grouped_matmul.padded_rows``)
        grid = -(-rows.shape[0] * self.experts_per_token
                 // EXPERT_TILE_ROWS) + self.held[1]
        counts = jnp.stack([stats["rows_total"], stats["rows_held"],
                            stats["max_expert_rows"], stats["live_experts"],
                            stats["live_tiles"],
                            jnp.asarray(grid, jnp.float32)])
        return a + y.reshape(a.shape), counts.astype(jnp.int32)

    def attn_out(self, params, layer, ctx, x):
        return self.attn_out_counted(params, layer, ctx, x, None)[0]

    def logits(self, params, x):
        with jax.named_scope("head"):
            return rms_norm(x, params["norm"], self.norm_eps) @ params["out"]
