"""Operations and bytes the serving attention kernel needs per layer and
tick in a model whose layers are of two kinds
(``serving/decode_attention.py:_ragged_pallas`` with and without a
``window``), from what the program counted of each kind's state
(``ServingMetrics``, a tick's mean over the window).

A full layer must read, once, the keys and values of every token its
sequences have cached (``full_kv_tokens_held``); a window layer those
inside its rows' windows only (``window_kv_tokens_live``: a slot's
``min(cached, window + rows - 1)``), at the K/V width ``kv_heads x
head_dim`` (float32 bytes).  Each query row makes two products over the
keys it sees at ITS layer's query width: a decoding slot's row over all of
them, and the rows of a prefill chunk are counted only among themselves
(r (r + 1) / 2 pairs a chunk of r rows), because the benchmark does not
see how far a prompt has got: the count errs low, and so does the share of
the roofline.  The bytes of a chunk's keys are read once here and once per
block of 8 rows by the kernel, which the share carries as a loss.
"""

from __future__ import annotations

from typing import Dict


def counts(kv_tokens: float, prefill_rows: float, heads: int, kv_heads: int,
           head_dim: int, kv_bytes_per: int = 4) -> Dict[str, float]:
    """For ONE layer and one tick: ``kv_tokens`` keys (and values) live
    for the layer's kind, ``prefill_rows`` rows of prefill chunks."""
    pairs = kv_tokens + prefill_rows * (prefill_rows + 1) / 2.0
    return {"flops": 4.0 * pairs * heads * head_dim,
            "bytes": 2.0 * kv_tokens * kv_heads * head_dim * kv_bytes_per}


def least_seconds(kv_tokens: float, prefill_rows: float, heads: int,
                  kv_heads: int, head_dim: int, peaks: dict
                  ) -> Dict[str, object]:
    c = counts(kv_tokens, prefill_rows, heads, kv_heads, head_dim)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
