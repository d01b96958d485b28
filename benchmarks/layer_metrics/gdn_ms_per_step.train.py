"""Device time a step of the whole delta-rule mixing layers: every
operation of the scope ``gdn`` (``ops/gated_delta.py gated_delta_net``:
projections, convolution, gates, scan, gated norm, output), forward,
recomputed and backward, over the steps dispatched in the traced window;
its share of ``device_ms_per_step.train`` is one division.  Scopes come
from ``harness/op_scopes.py`` (see ``gdn_scan_roofline.train``); the time
is the union of the matching intervals.  The share of each inner scope
goes to the log."""

from harness import op_scopes, trace as T
from harness.measure import say

INNER = ("gdn.proj", "gdn.conv", "gdn.scan", "gdn.out")


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train":
        return None
    steps = len(T.spans_named(tr, "dispatch"))
    if not steps:
        return None
    names = op_scopes.op_names(run["tracing"].file())
    per_step = lambda scope: 1e3 * op_scopes.seconds_under(  # noqa: E731
        tr, names, op_scopes.under(scope)) / steps
    total = per_step("gdn")
    if not total:
        return None
    say("gdn_ms_per_step.train by scope: " + ", ".join(
        f"{s} {per_step(s):.2f} ms" for s in INNER))
    return total
