"""Device time a tick of the served expert layers: every operation of the
scopes ``moe.route`` and ``moe.experts`` (``parallel/moe.py
moe_dropless``: the router's product, softmax and top-k, the sort of the
rows by expert, the three grouped products, the weighted sum back), over
the ``engine.step()`` spans of the traced window, on one chip; its share
of ``device_ms_per_tick.serve`` is one division.

A serving trace holds two step programs (a tick with and one without a
prefill chunk) whose instructions share names (``%fusion.83``), so an
operation's scope is looked up in the program it ran in: the ``XLA
Modules`` run that covers it names the program, and the trace file keeps
every program's HLO with each instruction's ``op_name``
(``harness/op_scopes.py`` reads the largest program alone).  The time is
the union of the matching intervals.  The share of each scope goes to the
log.  ``None`` without a trace, its ticks, or such a scope."""

import bisect
from typing import Dict

from harness import op_scopes as S, trace as T
from harness.measure import say

SCOPES = ("moe.route", "moe.experts")


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


def seconds_under(tr, names: Dict[str, Dict[str, str]], scope: str) -> float:
    lo, hi = tr.window
    chip = tr.chips[0]
    runs = sorted(chip.modules, key=lambda m: m.start)
    starts = [m.start for m in runs]
    inside = S.under(scope)
    hit = []
    for o in chip.ops:
        if o.start < lo or o.end > hi:
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        if i < 0 or o.start > runs[i].end:
            continue
        if inside(names.get(runs[i].name, {}).get(o.name, "")):
            hit.append((o.start, o.end))
    return T.clipped_seconds(T.merge(hit), lo, hi)


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    if not ticks:
        return None
    names = programs(run["tracing"].file())
    per = {s: 1e3 * seconds_under(tr, names, s) / ticks for s in SCOPES}
    if not sum(per.values()):
        return None
    say("moe_ms_per_tick.serve by scope: " + ", ".join(
        f"{s} {v:.3f} ms" for s, v in per.items()))
    return sum(per.values())
