"""Operations and bytes the training attention kernels need per layer and
step, from shapes (``ops/attention.py``: forward, dKV, dQ).

Under the causal mask inside each document there are n (n + 1) / 2 scores
per head; one product over them is 2 x that x head_dim operations.  The
forward kernel makes two products (scores, values).  The backward is two
kernels and neither keeps the scores, so each makes them again: dKV needs
four products (scores, dV, dP, dK), dQ three (scores, dP, dQ).  Bytes are
each operand read or written once, in the kernels' bfloat16.
"""

from __future__ import annotations

from typing import Dict, Sequence

PRODUCTS = {"forward": 2, "dkv": 4, "dq": 3}
# [tokens, hidden] operands: q k v in, o out | q k v o do in, dk dv out |
# q k v o do in, dq out
OPERANDS = {"forward": 4, "dkv": 7, "dq": 6}


def counts(doc_lengths: Sequence[int], hidden: int, bytes_per: int = 2
           ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"flops", "bytes"}} for ONE layer and one step."""
    pairs = sum(int(n) * (int(n) + 1) // 2 for n in doc_lengths)
    tokens = sum(int(n) for n in doc_lengths)
    return {k: {"flops": PRODUCTS[k] * 2.0 * pairs * hidden,
                "bytes": OPERANDS[k] * float(tokens) * hidden * bytes_per}
            for k in PRODUCTS}


def least_seconds(doc_lengths: Sequence[int], hidden: int, peaks: dict
                  ) -> Dict[str, object]:
    """The least time the three kernels of one layer could take on one
    chip, and which bound sets it."""
    total, bound = 0.0, {}
    for k, c in counts(doc_lengths, hidden).items():
        by_flops = c["flops"] / peaks["bf16_flops_per_s"]
        by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
        total += max(by_flops, by_bytes)
        bound[k] = "compute" if by_flops >= by_bytes else "memory"
    return {"seconds": total, "bound": bound}
