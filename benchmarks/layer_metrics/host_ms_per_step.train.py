"""Host time per training step: the step period in the traced window minus
the device's busy time in it (trace)."""

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train":
        return None
    n = len(T.spans_named(tr, "dispatch"))
    if n == 0:
        return None
    return 1e3 * (tr.window_s - T.busy_seconds(tr)) / n
