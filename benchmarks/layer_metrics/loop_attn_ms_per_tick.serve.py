"""Device time a tick of a looped stack's attention, over all its passes:
every operation under a scope ``attn`` and not under a scope ``proj``
(``engine.py _step_fn``: a layer's rotary turn, the scatter of its K/V
into the pass's own cache layer and the ragged kernel over it, inside
``pass<t>/l<l>/attn``; ``serving/looped_lm.py`` puts the q, k, v and
output projections under ``proj``, which
``loop_dense_ms_per_tick.serve`` reads), looked up in the program each ran
in, the union of the intervals over the ``engine.step()`` spans of the
traced window, on one chip (``harness/program_ops.py``).  ``None`` without
a trace, its ticks, or a scope ``pass0`` (a model of one pass names
none)."""

from harness import program_ops as P, trace as T


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    names = P.programs(run["tracing"].file())
    if not ticks or not P.ops_under(tr, names, "pass0"):
        return None
    dense = {id(o) for o in P.ops_under(tr, names, "proj")}
    ops = [o for o in P.ops_under(tr, names, "attn") if id(o) not in dense]
    if not ops:
        return None
    return 1e3 * P.union_seconds(tr, ops) / ticks
