"""Share of the (token, choice) pairs of the served expert layers that
landed on the experts held here: ``moe_rows_held / moe_rows_total`` from
``ServingMetrics`` over the window (``serving/window_moe_lm.py`` counts
both in every expert layer of every step).  With 128 of 256 experts held
and an even router it reads 50%.  ``None`` where the program counts no
held rows (a model that holds every expert)."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("moe_rows_total") \
            or "moe_rows_held" not in c:
        return None
    return 100.0 * c["moe_rows_held"] / c["moe_rows_total"]
