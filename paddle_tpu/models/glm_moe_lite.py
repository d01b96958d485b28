"""Decoder-only language model of the ``glm4_moe_lite`` shape (GLM-4.7-Flash;
the block is DeepSeek-V3's): multi-head latent attention, a leading dense
SwiGLU layer, then expert layers with a bias-corrected sigmoid router, a
shared expert and dropless routing, RMSNorm, rotary positions, an untied
head without bias, and a depth-1 multi-token-prediction (MTP) module that
shares the embedding and the head.

Built through the layer DSL for ``trainer.SGD``, as ``models/transformer``
is.  The expert layers are ONE RANK'S SHARE of an expert-parallel group:
``held_experts = (first, count)`` of ``n_routed_experts`` are held and
computed here, the router keeps its published width, and what the absent
experts would add is left out (``layer.moe_dropless``).  The vocabulary
may likewise be a slice.  No decode path here: serving reuses the layer
functions (``ops/mla.py``, ``parallel/moe.py``) when it gets its latent
page format.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import paddle_tpu as paddle
from paddle_tpu import layer
from paddle_tpu import topology as _topo
from paddle_tpu.attr import ParamAttr


def block(x, pos, *, name: str, dense_ffn: int, attn: dict, moe: dict,
          eps: float):
    """``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``; the FFN is a dense
    SwiGLU of width ``dense_ffn`` where that is set, else the expert layer."""
    a = layer.rms_norm(x, name=f"{name}_ln1", epsilon=eps)
    a = layer.mla_attention(a, pos, name=f"{name}_attn", epsilon=eps, **attn)
    x = layer.addto(input=[x, a], name=f"{name}_res1")
    f = layer.rms_norm(x, name=f"{name}_ln2", epsilon=eps)
    if dense_ffn:
        f = layer.swiglu_ffn(f, dense_ffn, name=f"{name}_ffn")
    else:
        f = layer.moe_dropless(f, name=f"{name}_moe", **moe)
    return layer.addto(input=[x, f], name=f"{name}_res2")


def build(vocab_size: int = 154880, hidden_size: int = 2048,
          n_dense_layers: int = 1, n_moe_layers: int = 46,
          num_heads: int = 20, q_lora_rank: int = 768,
          kv_lora_rank: int = 512, qk_nope_head_dim: int = 192,
          qk_rope_head_dim: int = 64, v_head_dim: int = 256,
          intermediate_size: int = 10240, moe_intermediate_size: int = 1536,
          n_routed_experts: int = 64,
          held_experts: Optional[Tuple[int, int]] = None,
          num_experts_per_tok: int = 4, n_shared_experts: int = 1,
          routed_scaling_factor: float = 1.8, mtp_layers: int = 1,
          mtp_weight: float = 0.3, rope_theta: float = 1e6,
          rms_norm_eps: float = 1e-5, max_len: int = 202752,
          remat: bool = False):
    """Returns (tokens, positions, target, logits, cost).  ``cost`` is the
    list ``[next-token loss, mtp_weight x MTP loss]`` when ``mtp_layers``
    is 1 (pass it whole to ``SGD(cost=...)``), else the one loss.

    Feeds as ``models/transformer``: ``tokens`` / ``target`` integer
    sequences (next-token targets), ``pos`` each token's position inside
    its sequence.  The MTP module predicts the token after next from the
    trunk's last hidden state and the next token's embedding; its targets
    are the ``target`` column moved one row up inside each sequence
    (``layer.next_token_cost``), so no further column is fed.
    ``held_experts`` defaults to all of them.  ``remat`` recomputes each
    block in the backward pass (``topology.remat_scope``), all of it but
    the router's scores, choice and row plan, which the expert layer tags
    to be kept (``topology.KEPT``; 3 MB a block of 8192 tokens at the
    published widths): latent attention, its flash kernels and the
    experts' products run a second time."""
    assert mtp_layers in (0, 1), "one MTP module at most"
    seq = paddle.data_type.integer_value_sequence
    tokens = layer.data(name="tokens", type=seq(vocab_size))
    pos = layer.data(name="pos", type=seq(max_len))
    target = layer.data(name="target", type=seq(vocab_size))
    attn = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                rope_theta=rope_theta)
    moe = dict(n_routed=n_routed_experts,
               held=held_experts or (0, n_routed_experts),
               expert_hidden=moe_intermediate_size,
               top_k=num_experts_per_tok, scaling=routed_scaling_factor,
               shared_hidden=n_shared_experts * moe_intermediate_size)

    def one(x, name, dense):
        scope = _topo.remat_scope(name) if remat \
            else contextlib.nullcontext()
        with scope:
            return block(x, pos, name=name, attn=attn, moe=moe,
                         dense_ffn=intermediate_size if dense else 0,
                         eps=rms_norm_eps)

    x = layer.embedding(input=tokens, size=hidden_size, name="tok_embed")
    for i in range(n_dense_layers + n_moe_layers):
        x = one(x, f"blk{i}", dense=i < n_dense_layers)
    head = ParamAttr(name="lm_head.w0")
    logits = layer.fc(input=layer.rms_norm(x, name="final_ln",
                                           epsilon=rms_norm_eps),
                      size=vocab_size, bias_attr=False, name="lm_head")
    cost = layer.next_token_cost(logits, target, publish="lm_loss",
                                 name="lm_cost")
    if not mtp_layers:
        return tokens, pos, target, logits, cost
    # h' = [RMSNorm(h_i) | RMSNorm(Emb(t_{i+1}))] W_eh, one block, the
    # module's own norm, the main model's head -> t_{i+2}
    nxt = layer.embedding(input=target, size=hidden_size, name="mtp_embed",
                          param_attr=ParamAttr(name="tok_embed.w"))
    h = layer.fc(input=layer.concat(
        [layer.rms_norm(x, name="mtp_hnorm", epsilon=rms_norm_eps),
         layer.rms_norm(nxt, name="mtp_enorm", epsilon=rms_norm_eps)],
        name="mtp_cat"), size=hidden_size, bias_attr=False,
        name="mtp_eh_proj")
    h = one(h, "mtp", dense=False)
    mtp_logits = layer.fc(input=layer.rms_norm(h, name="mtp_final_ln",
                                               epsilon=rms_norm_eps),
                          size=vocab_size, bias_attr=False, param_attr=head,
                          name="mtp_head")
    mtp_cost = layer.next_token_cost(mtp_logits, target, shift=1,
                                     weight=mtp_weight, publish="mtp_loss",
                                     name="mtp_cost")
    return tokens, pos, target, logits, [cost, mtp_cost]
