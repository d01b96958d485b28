"""Operations a GPT-2-family model needs per training step, from shapes.

Forward and backward, nothing recomputed: a matrix product of [T, a] by
[a, b] is 2 T a b forward and twice that backward.  Attention inside a
document of n tokens needs, under the causal mask, n (n + 1) / 2 scores per
head: two products (scores, values) of 2 d each forward, four backward.
Embedding lookups, norms, GELU and the softmax are left out (they are
under 1% at these widths), so the count errs low and a share of the peak
computed from it errs low too.
"""

from __future__ import annotations

from typing import Sequence


def matmul_params(hidden: int, ffn: int, layers: int, vocab: int) -> int:
    """Parameters that sit in matrix products, head included."""
    return layers * (4 * hidden * hidden + 2 * hidden * ffn) + hidden * vocab


def attention_flops(doc_lengths: Sequence[int], hidden: int, layers: int
                    ) -> float:
    """Forward + backward operations of attention over one step's
    documents: 3 x (2 products x 2 x n(n+1)/2 x hidden) per layer."""
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)
    return 3.0 * 2 * 2 * pairs * hidden * layers


def train_step_flops(doc_lengths: Sequence[int], hidden: int, ffn: int,
                     layers: int, vocab: int) -> float:
    tokens = sum(int(n) for n in doc_lengths)
    return 6.0 * matmul_params(hidden, ffn, layers, vocab) * tokens + \
        attention_flops(doc_lengths, hidden, layers)
