"""Operations and bytes the serving attention kernel needs per layer and
tick where K and V have fewer heads than the queries and a slot brings
several query rows (``serving/decode_attention.py:_ragged_pallas`` under a
block model: ``rows_per_slot`` rows a slot, each seeing the slot's whole
cache and block).

A decoding slot with c tokens in the cache (its current block among them)
must read its c keys and c values once, at the K/V width ``kv_heads x
head_dim`` (bytes), and each of its ``rows_per_slot`` query rows makes two
products over them at the query width (``4 c heads head_dim`` operations
a row).  Bytes are the LIVE keys and values of the slots that decode in
the tick, not the pool.  The rows of a prefill chunk are counted only
among themselves (r (r + 1) / 2 pairs per chunk of r rows, its r keys and
values read once), because the benchmark does not see how far a prompt
has got: the count errs low, and so does the share of the roofline.
"""

from __future__ import annotations

from typing import Dict


def counts(live_kv_tokens: int, prefill_rows: int, heads: int,
           kv_heads: int, head_dim: int, rows_per_slot: int = 1,
           kv_bytes_per: int = 4) -> Dict[str, float]:
    """For ONE layer and one tick."""
    pairs = rows_per_slot * live_kv_tokens \
        + prefill_rows * (prefill_rows + 1) // 2
    return {"flops": 4.0 * pairs * heads * head_dim,
            "bytes": 2.0 * (live_kv_tokens + prefill_rows) * kv_heads
            * head_dim * kv_bytes_per}


def least_seconds(live_kv_tokens: int, prefill_rows: int, heads: int,
                  kv_heads: int, head_dim: int, peaks: dict,
                  rows_per_slot: int = 1) -> Dict[str, object]:
    c = counts(live_kv_tokens, prefill_rows, heads, kv_heads, head_dim,
               rows_per_slot)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
