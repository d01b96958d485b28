"""Device time of the collective operations (all-reduce and its kin) per
tick on one chip, over the ticks of the traced window."""

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve" \
            or len(tr.chips) < 2:
        return None
    seconds, calls = T.op_seconds(tr, T.is_collective)
    n = len(T.spans_named(tr, "engine_step"))
    return 1e3 * seconds / n if n and calls else None
