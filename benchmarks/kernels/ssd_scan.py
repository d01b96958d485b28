"""Operations and bytes the state-space recurrence of one layer needs for
one serving tick, from shapes (``paddle_tpu/ops/ssd.py``: ``ssd_step`` for
the slots' decode rows, ``ssd_chunks`` for the bucket's chunks; the scope
``ssm.scan``).

Bytes, each moved once.  The state of a slot that has a row in the tick is
read and written (``heads x lanes x state`` float32, twice); a chunk that
BEGINS a sequence reads none (it starts from zeros) and writes one.  Every
live row brings ``xs`` and takes ``y`` (``heads x lanes`` each), ``B`` and
``C`` (``groups x state`` each) and ``dt`` (``heads``), float32.  The
states of slots without a row, the convolution before the scan and the
gated norm behind it are left out, so the count errs low.

Operations, of the chunked form over the chunks' rows only (a decode row's
update is elementwise, 4 operations an element of its state, far under the
bandwidth's bound and left out): a row reads the state it starts from
(``2 heads lanes state``) and adds to the one it leaves (the same); inside
a piece of ``chunk`` rows the masked ``C B^T`` (``2 groups chunk state`` a
row) and its product with the rows' inputs (``2 heads chunk lanes`` a
row).  Counted at the chip's bfloat16 peak although the program computes
them in float32 at the highest precision: the least time a chip could
take, not the program's own.

The least time is the larger of bytes over bandwidth and operations over
peak.  At the 34B's sizes (32 heads of 128, state 256, 2 groups, chunk
128) a slot's state is 4,194,304 B, so 64 decoding slots move 536,870,912
B a layer and tick, 2.15 GB (2.18 with the convolution's carry, which this
count leaves out) over 4 blocks: 2.6 ms of the chip's 819 GB/s; a chunk
row costs 5.4 MFLOP, so 1,024 of them 5.5 GFLOP a layer: 0.03 ms.
"""

from __future__ import annotations

from typing import Dict


def state_bytes(heads: int, lanes: int, state: int) -> int:
    """One slot's recurrence state in one layer, float32."""
    return heads * lanes * state * 4


def row_bytes(heads: int, lanes: int, state: int, groups: int) -> int:
    """What one live row brings and takes: xs, y, B, C, dt, float32."""
    return (2 * heads * lanes + 2 * groups * state + heads) * 4


def row_flops(heads: int, lanes: int, state: int, groups: int,
              chunk: int) -> float:
    """The chunked form's operations for one chunk row."""
    return (4.0 * heads * lanes * state        # from and to the state
            + 2.0 * groups * chunk * state     # C B^T
            + 2.0 * heads * chunk * lanes)     # its product with xs


def counts(decode_rows: float, prefill_rows: float, started: float,
           continued: float, *, heads: int, lanes: int, state: int,
           groups: int, chunk: int) -> Dict[str, float]:
    """{"flops", "bytes"} of ONE layer and one tick: ``decode_rows`` live
    decode rows (a slot each), ``prefill_rows`` live chunk rows in
    ``started`` chunks that begin a sequence and ``continued`` that go on
    with one."""
    per = state_bytes(heads, lanes, state)
    moved = (2.0 * decode_rows + 2.0 * continued + started) * per \
        + (decode_rows + prefill_rows) * row_bytes(heads, lanes, state,
                                                   groups)
    return {"flops": prefill_rows * row_flops(heads, lanes, state, groups,
                                              chunk),
            "bytes": moved}


def least_seconds(decode_rows: float, prefill_rows: float, started: float,
                  continued: float, peaks: dict, **sizes
                  ) -> Dict[str, object]:
    """The least time one layer's scan of one tick could take on one chip,
    and which bound sets it."""
    c = counts(decode_rows, prefill_rows, started, continued, **sizes)
    by_flops = c["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = c["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
