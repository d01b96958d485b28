"""Decoder-only language model of the ``qwen3_next`` shape
(Qwen3-Next-80B-A3B): gated delta-rule (linear-attention) layers with a
gated grouped-query attention layer as every ``full_attention_interval``-th,
each followed by an expert layer with a softmax router, dropless routing
and a gated shared expert; weighted RMSNorm, rotary positions on part of a
head, an untied head without bias.

Built through the layer DSL for ``trainer.SGD``, as ``models/glm_moe_lite``
is.  The expert layers are ONE RANK'S SHARE of an expert-parallel group:
``held_experts = (first, count)`` of ``num_experts`` are held and computed
here, the router keeps its published width, and what the absent experts
would add is left out (``layer.moe_dropless``).  The vocabulary may
likewise be a slice.  No decode path here: serving reuses the layer
functions (``ops/gated_delta.py``, ``ops/gated_attention.py``,
``parallel/moe.py``) when it gets a recurrent state beside its pages.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import paddle_tpu as paddle
from paddle_tpu import layer
from paddle_tpu import topology as _topo


def block(x, pos, *, name: str, full_attention: bool, attn: dict,
          delta: dict, moe: dict, eps: float, remat: bool):
    """``x += Mix(RMSNorm(x)); x += MoE(RMSNorm(x))``; ``Mix`` is gated
    attention where ``full_attention``, else the gated delta-rule layer.
    With ``remat`` each half is a recomputed segment of its own, so that
    the backward pass never holds the mixing layer's intermediates beside
    the expert layer's.  A segment keeps what its ops tag as dear to
    rebuild and small to hold (``topology.KEPT``): the delta-rule layer's
    projections, the scan's operands, output and chunk states (1.07 GB a
    layer at the 80B model's widths and 8192 tokens), so none of its
    kernels above the gated norm runs twice, and the router's scores,
    choice and row plan (18 MB), so a step routes once a layer; gated
    attention and the experts' products are recomputed whole."""
    def scope(part):
        return _topo.remat_scope(f"{name}_{part}") if remat \
            else contextlib.nullcontext()

    with scope("mix"):
        a = layer.rms_norm(x, name=f"{name}_ln1", epsilon=eps)
        if full_attention:
            a = layer.gated_attention(a, pos, name=f"{name}_attn",
                                      epsilon=eps, **attn)
        else:
            a = layer.gated_delta_net(a, name=f"{name}_gdn", epsilon=eps,
                                      **delta)
        x = layer.addto(input=[x, a], name=f"{name}_res1")
    with scope("moe"):
        f = layer.rms_norm(x, name=f"{name}_ln2", epsilon=eps)
        f = layer.moe_dropless(f, name=f"{name}_moe", **moe)
        return layer.addto(input=[x, f], name=f"{name}_res2")


def build(vocab_size: int = 151936, hidden_size: int = 2048,
          num_layers: int = 48, full_attention_interval: int = 4,
          num_heads: int = 16, num_kv_heads: int = 2, head_dim: int = 256,
          partial_rotary_factor: float = 0.25, linear_num_key_heads: int = 16,
          linear_num_value_heads: int = 32, linear_key_head_dim: int = 128,
          linear_value_head_dim: int = 128, linear_conv_kernel_dim: int = 4,
          moe_intermediate_size: int = 512,
          shared_expert_intermediate_size: int = 512, num_experts: int = 512,
          held_experts: Optional[Tuple[int, int]] = None,
          num_experts_per_tok: int = 10, rope_theta: float = 1e7,
          rms_norm_eps: float = 1e-6, max_len: int = 262144,
          remat: bool = False):
    """Returns (tokens, positions, target, logits, cost).

    Feeds as ``models/transformer``: ``tokens`` / ``target`` integer
    sequences (next-token targets), ``pos`` each token's position inside
    its sequence.  Layer ``l`` (from 0) is gated attention where ``(l + 1)
    % full_attention_interval == 0``, else a delta-rule layer.
    ``held_experts`` defaults to all of them.  ``remat`` recomputes each
    block in the backward pass, its two halves apart, but for what
    ``block`` says is kept (``topology.remat_scope``)."""
    seq = paddle.data_type.integer_value_sequence
    tokens = layer.data(name="tokens", type=seq(vocab_size))
    pos = layer.data(name="pos", type=seq(max_len))
    target = layer.data(name="target", type=seq(vocab_size))
    attn = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim,
                rotary_dim=int(head_dim * partial_rotary_factor),
                rope_theta=rope_theta)
    delta = dict(num_k_heads=linear_num_key_heads,
                 num_v_heads=linear_num_value_heads,
                 head_k_dim=linear_key_head_dim,
                 head_v_dim=linear_value_head_dim,
                 conv_kernel=linear_conv_kernel_dim)
    moe = dict(n_routed=num_experts,
               held=held_experts or (0, num_experts),
               expert_hidden=moe_intermediate_size,
               top_k=num_experts_per_tok, routing="softmax",
               shared_hidden=shared_expert_intermediate_size,
               shared_gated=True)
    x = layer.embedding(input=tokens, size=hidden_size, name="tok_embed")
    for i in range(num_layers):
        x = block(x, pos, name=f"blk{i}", attn=attn, delta=delta, moe=moe,
                  eps=rms_norm_eps, remat=remat,
                  full_attention=(i + 1) % full_attention_interval == 0)
    logits = layer.fc(input=layer.rms_norm(x, name="final_ln",
                                           epsilon=rms_norm_eps),
                      size=vocab_size, bias_attr=False, name="lm_head")
    cost = layer.next_token_cost(logits, target, publish="lm_loss",
                                 name="lm_cost")
    return tokens, pos, target, logits, cost
