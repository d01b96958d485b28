"""A trace's device operations selected by the scope they ran under, in a
trace that holds several programs.

A serving trace holds two step programs (a tick with and one without a
prefill chunk) whose instructions share names (``%fusion.83``), so an
operation's ``op_name`` is looked up in the program it ran in: the ``XLA
Modules`` run that covers it names the program, and the trace file keeps
every program's HLO with each instruction's ``op_name``
(``harness/op_scopes.py`` reads the wire format and, in ``op_names``, the
largest program alone; ``layer_metrics/moe_ms_per_tick.serve.py`` was the
first to look per program).  A reader that finds no HLO, no module run or
no such scope gets nothing and returns ``None``.
"""

from __future__ import annotations

import bisect
from typing import Callable, Dict, List, Tuple

from harness import op_scopes as S, trace as T


def programs(path: str) -> Dict[str, Dict[str, str]]:
    """{program as an ``XLA Modules`` event names it: {instruction:
    op_name}} for every program whose HLO the trace file keeps."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for plane in S._sub(space, 1):
        if next(S._sub(plane, 2), b"").decode() != S.METADATA_PLANE:
            continue
        for entry in S._sub(plane, 4):
            for meta in S._sub(entry, 2):
                name = next(S._sub(meta, 2), b"").decode()
                for stat in S._sub(meta, 5):
                    for proto in S._sub(stat, 6):
                        for module in S._sub(proto, 1):
                            out[name] = S._module_op_names(module)
    return out


def ops_under(tr: T.Trace, names: Dict[str, Dict[str, str]], scope: str,
              pred: Callable[[T.Op], bool] = lambda o: True
              ) -> List[T.Op]:
    """Chip 0's operations inside the window that satisfy ``pred`` and
    whose ``op_name``, in the program each ran in, lies under ``scope``."""
    lo, hi = tr.window
    chip = tr.chips[0]
    runs = sorted(chip.modules, key=lambda m: m.start)
    starts = [m.start for m in runs]
    inside = S.under(scope)
    out = []
    for o in chip.ops:
        if o.start < lo or o.end > hi or not pred(o):
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        if i < 0 or o.start > runs[i].end:
            continue
        if inside(names.get(runs[i].name, {}).get(o.name, "")):
            out.append(o)
    return out


def union_seconds(tr: T.Trace, ops: List[T.Op]) -> float:
    """Seconds of the window in which one of ``ops`` ran (a ``while`` and
    its body overlap: the union, not the sum)."""
    lo, hi = tr.window
    return T.clipped_seconds(T.merge([(o.start, o.end) for o in ops]),
                             lo, hi)


def kernel_seconds(tr: T.Trace, names: Dict[str, Dict[str, str]],
                   scope: str, kernel: str) -> Tuple[float, int]:
    """(device seconds, calls) of the Pallas kernels named ``kernel`` that
    ran under ``scope``."""
    ops = ops_under(tr, names, scope,
                    lambda o: T.is_kernel(o) and kernel in o.name)
    return sum(o.seconds for o in ops), len(ops)


def scope_ms_per_tick(run, scope: str):
    """Device milliseconds a tick under ``scope``: the union of its
    operations' intervals over the ``engine.step()`` spans of the traced
    window, on one chip.  ``None`` without a trace, its ticks, or such a
    scope."""
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    if not ticks:
        return None
    ops = ops_under(tr, programs(run["tracing"].file()), scope)
    if not ops:
        return None
    return 1e3 * union_seconds(tr, ops) / ticks
