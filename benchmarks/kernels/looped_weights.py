"""Bytes of parameters a decode tick of a looped decoder must read, from
shapes (``paddle_tpu/serving/looped_lm.py``: the ``ouro`` family's stack of
``num_hidden_layers`` weight layers applied ``total_ut_steps`` times a
tick over the same leaves; scopes ``proj``, ``ffn``, ``close``, ``head``).

A chip's on-chip memory holds a few tens of MB, a layer's matrices 205 MB
at the 2.6B's widths, so every pass reads every matrix of the stage from
HBM again: ``total_ut_steps x num_hidden_layers x (4 E H D + 3 E F)``
float32 (q, k, v, o and the SwiGLU's three), and the untied head ``E V``
once (it is applied to the last pass's rows alone).  The gains, the gate
and the embedding's gathered rows are left out, and so are the rows'
activations, so the count errs low; a tick that carries a prefill chunk
is bound by its products, not by these bytes, and reads as a smaller
share.  Operations are not counted: at the 32 to 64 rows of a decode tick
the products take a twentieth of the time the bytes do.

At the 2.6B's widths (E 2048, 16 heads of 128, F 5632) with 8 layers held
and 4 passes: a layer 51,380,224 parameters = 205,520,896 B, a pass
1,644,167,168 B, four 6,576,668,672 B, the head 402,653,184 B:
6,979,321,856 B a tick, 8.5 ms at 819 GB/s.
"""

from __future__ import annotations

from typing import Dict

KEYS = ("hidden_size", "num_attention_heads", "head_dim",
        "intermediate_size", "vocab_size", "num_hidden_layers",
        "total_ut_steps")


def layer_bytes(hidden: int, heads: int, head_dim: int, ffn: int,
                bytes_per: int = 4) -> int:
    """One weight layer's matrices: q, k, v, o and the SwiGLU's three."""
    return (4 * hidden * heads * head_dim + 3 * hidden * ffn) * bytes_per


def counts(*, hidden_size: int, num_attention_heads: int, head_dim: int,
           intermediate_size: int, vocab_size: int, num_hidden_layers: int,
           total_ut_steps: int, bytes_per: int = 4) -> Dict[str, float]:
    """{"bytes", "passes_bytes", "head_bytes"} of ONE tick."""
    a_pass = num_hidden_layers * layer_bytes(
        hidden_size, num_attention_heads, head_dim, intermediate_size,
        bytes_per)
    head = hidden_size * vocab_size * bytes_per
    return {"bytes": float(total_ut_steps * a_pass + head),
            "passes_bytes": float(total_ut_steps * a_pass),
            "head_bytes": float(head)}


def least_seconds(peaks: dict, **sizes) -> Dict[str, object]:
    """The least time one tick's parameter reads could take on one chip."""
    return {"seconds": counts(**sizes)["bytes"] / peaks["hbm_bytes_per_s"],
            "bound": "memory"}
