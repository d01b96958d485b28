"""Share of the engine's slots that decoded, over the window's ticks:
``decode_slots / (ticks x max_slots)`` from ``ServingMetrics``."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("ticks"):
        return None
    return 100.0 * c["decode_slots"] / (c["ticks"] * run["max_slots"])
