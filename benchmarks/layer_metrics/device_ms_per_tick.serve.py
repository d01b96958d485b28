"""Device-busy time per tick: the median, over the ticks of the traced
window, of the busy time inside ``engine.step()``'s span."""

import statistics

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    rows = T.per_span(tr, "engine_step")
    return 1e3 * statistics.median(b for _, b in rows) if rows else None
