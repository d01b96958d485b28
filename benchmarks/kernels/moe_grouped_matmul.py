"""Operations and bytes the grouped matrix products of one dropless expert
layer need for one step, from shapes (``paddle_tpu/ops/grouped_matmul.py``:
``moe_gmm``, ``moe_tgmm``).

``rows`` (token, choice) pairs landed on the held experts.  Each goes
through three products (gate and up, hidden -> width; down, width ->
hidden), and each product has two gradients (the rows', by the transposed
matrix, and the matrix's, rows^T x rows): nine products of ``2 x rows x
hidden x width`` operations, nothing recomputed.  Bytes are each operand
read or written once: rows in the operands' bfloat16, the products'
results in float32 forward and bfloat16 backward, every held expert's
matrix read once in bfloat16, its gradient written once in float32.  Rows
of padding (a group is filled to a multiple of the row tile) are not
counted, so the share of the roofline carries the padding as a loss.
"""

from __future__ import annotations

from typing import Dict

# (kernel, left width, right width, bytes of an element of the result)
PRODUCTS = (
    ("gate", "hidden", "width", 4), ("up", "hidden", "width", 4),
    ("down", "width", "hidden", 4),
    ("gate_drows", "width", "hidden", 2), ("up_drows", "width", "hidden", 2),
    ("down_drows", "hidden", "width", 2),
)
MATRIX_GRADS = ("gate_dmatrix", "up_dmatrix", "down_dmatrix")


def counts(rows: float, hidden: int, width: int, held: int,
           bytes_per: int = 2) -> Dict[str, Dict[str, float]]:
    """{product: {"flops", "bytes"}} for ONE layer and one step."""
    size = {"hidden": hidden, "width": width}
    flops = 2.0 * rows * hidden * width
    matrix = float(held) * hidden * width
    out = {}
    for name, left, right, out_bytes in PRODUCTS:
        out[name] = {"flops": flops,
                     "bytes": rows * size[left] * bytes_per
                     + matrix * bytes_per + rows * size[right] * out_bytes}
    for name in MATRIX_GRADS:
        out[name] = {"flops": flops,
                     "bytes": rows * (hidden + width) * bytes_per
                     + matrix * 4}
    return out


def least_seconds(rows: float, hidden: int, width: int, held: int,
                  peaks: dict) -> Dict[str, object]:
    """The least time the nine products of one layer could take on one
    chip, and which bound sets each."""
    total, bound = 0.0, {}
    for k, c in counts(rows, hidden, width, held).items():
        by_flops = c["flops"] / peaks["bf16_flops_per_s"]
        by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
        total += max(by_flops, by_bytes)
        bound[k] = "compute" if by_flops >= by_bytes else "memory"
    return {"seconds": total, "bound": bound}
