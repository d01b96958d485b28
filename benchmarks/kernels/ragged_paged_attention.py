"""Operations and bytes the serving attention kernel needs per layer and
tick, from shapes (``serving/decode_attention.py:_ragged_pallas``).

A decoding sequence with c tokens in the cache has one query row: it must
read its c keys and c values once (bytes) and make two products over them
(4 c hidden operations).  Bytes are the LIVE keys and values of the
sequences that decode in the tick, not the pool.  The rows of a prefill
chunk are counted only among themselves (r (r + 1) / 2 pairs per chunk of
r rows), because the benchmark does not see how far a prompt has got: the
count errs low, and so does the share of the roofline.
"""

from __future__ import annotations

from typing import Dict


def counts(live_kv_tokens: int, prefill_rows: int, hidden: int,
           kv_bytes_per: int = 4, tp: int = 1) -> Dict[str, float]:
    """For ONE layer, one tick and one chip of ``tp`` (heads are split)."""
    pairs = live_kv_tokens + prefill_rows * (prefill_rows + 1) // 2
    return {"flops": 4.0 * pairs * hidden / tp,
            "bytes": 2.0 * (live_kv_tokens + prefill_rows) * hidden
            * kv_bytes_per / tp}


def least_seconds(live_kv_tokens: int, prefill_rows: int, hidden: int,
                  peaks: dict, tp: int = 1) -> Dict[str, object]:
    c = counts(live_kv_tokens, prefill_rows, hidden, tp=tp)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
