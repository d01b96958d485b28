"""Device time a tick of a looped stack's matrices, over all its passes:
every operation under a scope ``proj`` (a layer's q, k, v and output
projections with their norms), ``ffn`` (the SwiGLU between its two norms)
or ``close`` (the final norm that closes a pass, and the exit gate), as
``serving/looped_lm.py`` and ``engine.py _step_fn`` name them inside
``pass<t>``, looked up in the program each ran in, the union of the
intervals over the ``engine.step()`` spans of the traced window, on one
chip (``harness/program_ops.py``).  The head (scope ``head``) is not in
it: ``loop_weights_roofline.serve`` adds it.  ``None`` without a trace,
its ticks, or a scope ``pass0`` (a model of one pass names none)."""

from harness import program_ops as P, trace as T

SCOPES = ("proj", "ffn", "close")


def dense_ops(tr, names, scopes=SCOPES):
    """The window's operations under any of ``scopes``, each once."""
    seen = {}
    for scope in scopes:
        for o in P.ops_under(tr, names, scope):
            seen[id(o)] = o
    return list(seen.values())


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    ticks = len(T.spans_named(tr, "engine_step"))
    names = P.programs(run["tracing"].file())
    if not ticks or not P.ops_under(tr, names, "pass0"):
        return None
    ops = dense_ops(tr, names)
    if not ops:
        return None
    return 1e3 * P.union_seconds(tr, ops) / ticks
