"""Share of its roofline that a looped stack's parameter traffic reaches:
the least time the traced window's ticks could take to read what they
must (``kernels/looped_weights.py``: ``total_ut_steps`` times the stage's
block matrices and the head once, float32, over the chip's bandwidth),
over the device time in which the parameters move or are multiplied, the
union of two kinds of interval over the ``engine.step()`` spans of the
traced window, on one chip: the operations under the scopes ``proj``,
``ffn``, ``close`` (``loop_dense_ms_per_tick.serve``'s) and ``head``; and
the PREFETCHES of the step's ``params`` argument, from each asynchronous
``slice-start`` / ``copy-start`` of a parameter to its ``-done`` (XLA
brings the matrices into on-chip memory in slices, beside the attention
kernel, and the products then read them there: measured in PR 47, the
products alone take less than the bytes need, 7.8 ms against 8.5).  A
tick that carries a prefill chunk is bound by its products and pulls the
share down; the gains, the gate and the activations are not counted, so
the share errs low.  ``None`` where the configuration does not say the
family's sizes, without a trace or its ticks, or without a scope
``pass0``."""

import re

from harness import cells, program_ops as P, trace as T
from harness.measure import say

STARTED = re.compile(r"(%[\w\-]+-start[.\d]*)\)")


def param_prefetches(tr):
    """``[(start of the -start, end of its -done)]`` of the window's
    asynchronous slices and copies whose source is a leaf of the step's
    ``params`` argument (``%params__l0_wv__.1``), on chip 0."""
    lo, hi = tr.window
    open_, out = {}, []
    for o in sorted(tr.chips[0].ops, key=lambda o: o.start):
        if o.start < lo or o.end > hi:
            continue
        if "-start" in o.name and "%params_" in o.text:
            open_[o.name] = o.start
        elif "-done" in o.name:
            m = STARTED.search(o.text)
            if m and m.group(1) in open_:
                out.append((open_.pop(m.group(1)), o.end))
    return out


def read(run):
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr, cfg = run.get("trace"), run["cell"].config
    weights = cells.kernel("looped_weights")
    if tr is None or not tr.chips or any(k not in cfg for k in weights.KEYS):
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    names = P.programs(run["tracing"].file())
    if not ticks or not P.ops_under(tr, names, "pass0"):
        return None
    dense = run["cell"].layer_metric("loop_dense_ms_per_tick.serve")
    ops = dense.dense_ops(tr, names, dense.SCOPES + ("head",))
    fetched = param_prefetches(tr)
    lo, hi = tr.window
    seconds = T.clipped_seconds(T.merge(
        [(o.start, o.end) for o in ops] + fetched), lo, hi)
    if not seconds:
        return None
    least = weights.least_seconds(run["peaks"],
                                  **{k: cfg[k] for k in weights.KEYS})
    say(f"loop_weights_roofline.serve: {1e3 * seconds / ticks:.3f} ms a "
        f"tick in which parameters move or are multiplied "
        f"({1e3 * P.union_seconds(tr, ops) / ticks:.3f} under proj, ffn, "
        f"close and head; {len(fetched) / ticks:.1f} prefetches a tick) "
        f"over {ticks} ticks against a least {1e3 * least['seconds']:.3f} ms")
    return 100.0 * least["seconds"] * ticks / seconds
