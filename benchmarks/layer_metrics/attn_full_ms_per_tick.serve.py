"""Device time a tick of the full layers' attention: every operation
under the scope ``attn.full`` (``engine.py _step_fn``: a layer's
projections, rotary, the scatter of its K/V into its kind's pages and the
ragged kernel, inside ``l<l>/attn``), looked up in the program each ran
in, the union of the intervals over the ``engine.step()`` spans of the
traced window, on one chip (``harness/program_ops.py
scope_ms_per_tick``).  ``None`` without a trace, its ticks, or such a
scope (a model of one kind names no kind)."""

from harness import program_ops as P


def read(run):
    return P.scope_ms_per_tick(run, "attn.full")
