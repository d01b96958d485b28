"""Operations a ``qwen3_next`` model needs per training step, from shapes.

Forward and backward, nothing recomputed: a matrix product of [T, a] by
[a, b] is 2 T a b forward and twice that backward.  Per token the
products are: a delta-rule block's three projections (``w_qkvz``,
``w_ba``, ``wo``) or an attention block's four (``wq`` with the gate,
``wk``, ``wv``, ``wo``); in every block the router, the shared expert's
three with its gate and the routed experts' three at the EXPECTED share of
rows that lands on the experts held here (``top_k x held / n_routed`` of a
token); the head once.  Attention inside a document of n tokens needs n (n
+ 1) / 2 scores per query head: two products over ``head_dim`` forward,
four backward.  The delta rule's own products are
``kernels/gated_delta_rule.py``'s, forward and twice that backward.
Embedding lookups, norms, rotary, the convolution, SiLU, the softmax and
the sort are left out, so the count errs low.
"""

from __future__ import annotations

from typing import Sequence

from harness import cells

SCAN = cells.kernel("gated_delta_rule")


def block_kinds(s: dict):
    """(delta-rule blocks, attention blocks) among ``layers``."""
    attention = s["layers"] // s["interval"]
    return s["layers"] - attention, attention


def delta_params(s: dict) -> int:
    nq, nv = s["k_heads"] * s["dk"], s["v_heads"] * s["dv"]
    return s["hidden"] * (2 * nq + 2 * nv + 2 * s["v_heads"]) \
        + nv * s["hidden"]


def attention_params(s: dict) -> int:
    h, d = s["heads"], s["head_dim"]
    return s["hidden"] * (2 * h * d + 2 * s["kv_heads"] * d) \
        + h * d * s["hidden"]


def expert_layer_params(s: dict) -> float:
    """Matrix parameters a token meets in one expert layer."""
    routed = s["top_k"] * s["held"] / s["n_routed"]
    return (s["hidden"] * s["n_routed"]
            + 3 * s["hidden"] * s["shared_width"] + s["hidden"]
            + routed * 3 * s["hidden"] * s["expert_width"])


def active_params(s: dict) -> float:
    """Matrix parameters a token meets in one step, the head included."""
    delta, attention = block_kinds(s)
    return (delta * delta_params(s) + attention * attention_params(s)
            + s["layers"] * expert_layer_params(s)
            + s["hidden"] * s["vocab"])


def attention_flops(doc_lengths: Sequence[int], s: dict) -> float:
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)
    per_pair = 2.0 * s["heads"] * 2 * s["head_dim"]
    return 3.0 * per_pair * pairs * block_kinds(s)[1]


def scan_flops(doc_lengths: Sequence[int], s: dict) -> float:
    c = SCAN.counts(doc_lengths, s["k_heads"], s["v_heads"], s["dk"],
                    s["dv"])
    return (c["forward"]["flops"] + c["backward"]["flops"]) \
        * block_kinds(s)[0]


def train_step_flops(doc_lengths: Sequence[int], s: dict) -> float:
    tokens = sum(int(n) for n in doc_lengths)
    return 6.0 * active_params(s) * tokens \
        + attention_flops(doc_lengths, s) + scan_flops(doc_lengths, s)
