"""Operations and bytes the gated delta rule of one layer needs for one
step, from shapes (``paddle_tpu/ops/gated_delta.py gated_delta_rule``, the
scope ``gdn.scan``): the chunked form at chunk ``C``.

Per chunk, forward: ``K K^T`` and ``Q K^T`` once a KEY head (``2 C^2 dk``
operations each); once a VALUE head the inverse of the unit-lower-
triangular ``I + A`` by substitution (``C^3 / 3`` multiply-adds), its
products with ``beta a k`` and ``beta v`` (``2 C^2 dk``, ``2 C^2 dv``),
the three products with the state (``W S``, ``q S``, ``k^T u``: ``2 C dk
dv`` each) and the scores' product with the updates (``2 C^2 dv``).  With
as many key heads as value heads that is ``2 C^2 (3 dk + 2 dv) + 6 C dk
dv`` a chunk and head, beside the solve.  The backward pass is twice the
forward; nothing recomputed.  A sequence of n tokens has ``ceil(n / C)``
chunks.  Decays, masks, the exponentials and the convolution before it are
left out, so the count errs low.

Bytes, each operand read or written once: forward q, k, v in and o out in
the products' bfloat16, g and beta in float32; backward the same inputs
and o's gradient in, the inputs' gradients out.
"""

from __future__ import annotations

from typing import Dict, Sequence

CHUNK = 64


def chunk_flops(k_heads: int, v_heads: int, dk: int, dv: int,
                chunk: int = CHUNK) -> float:
    """Forward operations of one chunk over all heads."""
    c = float(chunk)
    per_key_head = 2 * (2 * c * c * dk)                  # K K^T, Q K^T
    per_value_head = (2 * c ** 3 / 3                     # the solve
                      + 2 * c * c * (dk + 2 * dv)        # W, U, P u
                      + 6 * c * dk * dv)                 # W S, q S, k^T u
    return k_heads * per_key_head + v_heads * per_value_head


def counts(doc_lengths: Sequence[int], k_heads: int, v_heads: int, dk: int,
           dv: int, chunk: int = CHUNK, bytes_per: int = 2
           ) -> Dict[str, Dict[str, float]]:
    """{pass: {"flops", "bytes"}} for ONE layer and one step."""
    chunks = sum(-(-int(n) // chunk) for n in doc_lengths)
    tokens = float(sum(int(n) for n in doc_lengths))
    forward = chunks * chunk_flops(k_heads, v_heads, dk, dv, chunk)
    inputs = tokens * ((2 * k_heads * dk + v_heads * dv) * bytes_per
                       + 2 * v_heads * 4)
    out = tokens * v_heads * dv * bytes_per
    return {"forward": {"flops": forward, "bytes": inputs + out},
            "backward": {"flops": 2.0 * forward,
                         "bytes": 2.0 * inputs + out}}


def least_seconds(doc_lengths: Sequence[int], k_heads: int, v_heads: int,
                  dk: int, dv: int, peaks: dict) -> Dict[str, object]:
    """The least time the forward and the backward pass of one layer could
    take on one chip, and which bound sets each."""
    total, bound = 0.0, {}
    for k, c in counts(doc_lengths, k_heads, v_heads, dk, dv).items():
        by_flops = c["flops"] / peaks["bf16_flops_per_s"]
        by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
        total += max(by_flops, by_bytes)
        bound[k] = "compute" if by_flops >= by_bytes else "memory"
    return {"seconds": total, "bound": bound}
