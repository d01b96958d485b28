"""Device time a step of the whole expert layers: every operation under
the scopes ``moe.route``, ``moe.experts`` and ``moe.shared``
(``parallel/moe.py moe_dropless``: the router and the row plan; dispatch,
the grouped products, SiLU and combine; the shared expert), forward,
recomputed and backward, over the steps dispatched in the traced window;
its share of ``device_ms_per_step.train`` is one division.  Scopes come
from ``harness/op_scopes.py`` (see ``gdn_scan_roofline.train``), which
reads every computation of the step's program, a ``conditional``'s
branches among them; the time is the union of the matching intervals, so a
``conditional`` and the operations of the branch it took count once.  The
share of each scope goes to the log, and with it the program's count of
the steps each layer ran at each length of its sorted buffer
(``moe_rung_steps_total``), where it publishes one."""

from harness import op_scopes, trace as T
from harness.measure import say

INNER = ("moe.route", "moe.experts", "moe.shared")


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train":
        return None
    steps = len(T.spans_named(tr, "dispatch"))
    if not steps:
        return None
    names = op_scopes.op_names(run["tracing"].file())
    inside = [op_scopes.under(scope) for scope in INNER]
    per_step = lambda tests: 1e3 * op_scopes.seconds_under(  # noqa: E731
        tr, names, lambda op: any(t(op) for t in tests)) / steps
    total = per_step(inside)
    if not total:
        return None
    say("moe_ms_per_step.train by scope: " + ", ".join(
        f"{s} {per_step([t]):.2f} ms" for s, t in zip(INNER, inside)))
    rungs = {k: v for k, v in sorted(run["counters"].items())
             if k.startswith("moe_rung_steps_total{")}
    if rungs:
        say("moe_rung_steps_total over the window's "
            f"{run['counters']['steps']} steps: " + ", ".join(
                f"{k[len('moe_rung_steps_total'):]} {v:.0f}"
                for k, v in rungs.items()))
    return total
