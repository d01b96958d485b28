"""Device-busy time per training step: the union of the intervals in which
an operation ran, over the steps dispatched in the traced window."""

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train":
        return None
    n = len(T.spans_named(tr, "dispatch"))
    return 1e3 * T.busy_seconds(tr) / n if n else None
