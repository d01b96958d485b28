"""Operations a ``glm4_moe_lite`` model needs per training step, from
shapes.

Forward and backward, nothing recomputed: a matrix product of [T, a] by
[a, b] is 2 T a b forward and twice that backward.  Per token the
products are: latent attention's five projections in every block; the
dense SwiGLU's three in the dense blocks; in an expert block the router,
the shared expert's three and the routed experts' three at the EXPECTED
share of rows that lands on the experts held here (``top_k x held /
n_routed`` of a token); the MTP module's ``eh_proj`` and its one expert
block; the head once for the trunk and once for the MTP module.
Attention inside a document of n tokens needs n (n + 1) / 2 scores per
head: two products (scores over nope + rope, values over v) forward, four
backward.  Embedding lookups, norms, rotary, SiLU, the sigmoid, the sort
and the softmax are left out, so the count errs low.
"""

from __future__ import annotations

from typing import Sequence


def attention_params(s: dict) -> int:
    h = s["heads"]
    return (s["hidden"] * s["q_rank"]
            + s["q_rank"] * h * (s["nope"] + s["rope"])
            + s["hidden"] * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["v"])
            + h * s["v"] * s["hidden"])


def expert_block_params(s: dict) -> float:
    """Matrix parameters a token meets in one expert block."""
    ffn = 3 * s["hidden"] * s["expert_width"]
    routed = s["top_k"] * s["held"] / s["n_routed"]
    return (attention_params(s) + s["hidden"] * s["n_routed"]
            + s["shared"] * ffn + routed * ffn)


def active_params(s: dict) -> float:
    """Matrix parameters a token meets in one step, both heads included."""
    dense = attention_params(s) + 3 * s["hidden"] * s["dense_width"]
    total = s["dense_layers"] * dense + s["moe_layers"] * \
        expert_block_params(s) + s["hidden"] * s["vocab"]
    if s["mtp"]:
        total += 2 * s["hidden"] * s["hidden"] + expert_block_params(s) \
            + s["hidden"] * s["vocab"]
    return total


def attention_flops(doc_lengths: Sequence[int], s: dict) -> float:
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)
    blocks = s["dense_layers"] + s["moe_layers"] + s["mtp"]
    per_pair = 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v"])
    return 3.0 * per_pair * pairs * blocks


def train_step_flops(doc_lengths: Sequence[int], s: dict) -> float:
    tokens = sum(int(n) for n in doc_lengths)
    return 6.0 * active_params(s) * tokens + attention_flops(doc_lengths, s)
