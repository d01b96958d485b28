"""Device time per tick, on one chip, of the traced window's pure
data-movement operations: a ``slice``, ``reshape``, ``copy``,
``transpose``, ``dynamic-slice`` or ``concatenate`` that runs as an
operation of its own (also as an async ``-start`` / ``-done`` pair), not
inside a fusion, a kernel or a collective.  In a serving step these are
copies of the KV pool or of a layer of it (``engine._attend`` slicing a
layer out, ``decode_attention._ragged_call`` re-tiling it): bytes moved
to hand the attention kernel what already lay in HBM.  0 where the
window has no such operation; ``None`` without a trace or its ticks."""

import re

from harness import trace as T

MOVES = ("slice", "reshape", "copy", "transpose", "dynamic-slice",
         "concatenate")


def is_move(o) -> bool:
    """By the instruction's name (``%slice.5``, ``%copy-done.2``): an
    async copy's opcode is ``async-done``, a fused one is named for its
    fusion (``%copy_fusion.1``) and is not counted."""
    kind = re.sub(r"(-start|-done)?[.\d]*$", "", o.name.lstrip("%"))
    return kind in MOVES and o.opcode != "fusion" and not T.is_kernel(o)


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    n = len(T.spans_named(tr, "engine_step"))
    if not n:
        return None
    seconds, _ = T.op_seconds(tr, is_move)
    return 1e3 * seconds / n
