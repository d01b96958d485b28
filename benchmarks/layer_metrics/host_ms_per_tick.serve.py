"""Host time per tick: the median, over the ticks of the traced window,
of ``engine.step()``'s wall time (the benchmark's own span) minus the
device-busy time inside it."""

import statistics

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    rows = T.per_span(tr, "engine_step")
    return 1e3 * statistics.median(a - b for a, b in rows) if rows else None
