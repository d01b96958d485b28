"""The pass a decode row of a looped model would leave at were the exit
threshold lower, as the exit gate sees it: ``exit_step_milli / exit_rows /
1000`` from ``ServingMetrics`` over the window.  The compiled step reads
the gate behind every pass on its decode rows, makes the distribution
``p_t = lam_t prod_{j<t} (1 - lam_j)`` (the last pass taking what is left)
and sums the expectation ``sum_t t p_t`` in thousandths
(``engine.py _loop_counts``); between 1 and ``total_ut_steps``.  At the
published threshold 1.0 every row runs every pass whatever this reads: it
says what a lower threshold would have to gain.  ``None`` where the
program counts no such rows (a model without passes or without a
gate)."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("exit_rows"):
        return None
    return c["exit_step_milli"] / c["exit_rows"] / 1e3
