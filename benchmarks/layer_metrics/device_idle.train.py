"""Share of the traced window in which no operation ran on the device
(averaged over the chips): 1 - busy / window."""

from harness import trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train" or not tr.window_s:
        return None
    return 100.0 * (1.0 - T.busy_seconds(tr) / tr.window_s)
