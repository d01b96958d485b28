"""Device time a tick of the state-space branch: every operation under
the scope ``ssm`` (``serving/hybrid_ssm_lm.py mix``: the branch's input
projection, the convolution with its carry, the recurrence in its two
forms with the slots' states in and out, the gated norm and the output
projection, inside ``l<l>``), looked up in the program each ran in, the
union of the intervals (a ``while`` and its body overlap) over the
``engine.step()`` spans of the traced window, on one chip
(``harness/program_ops.py scope_ms_per_tick``).  ``None`` without a
trace, its ticks, or such a scope (a model without the branch)."""

from harness import program_ops as P


def read(run):
    return P.scope_ms_per_tick(run, "ssm")
