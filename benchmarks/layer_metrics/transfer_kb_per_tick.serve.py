"""Kilobytes a tick moves between host and device: the step's nine input
arrays up, its two logits arrays down
(``ServingMetrics.h2d_bytes + d2h_bytes``), over the window's ticks."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("ticks") \
            or "h2d_bytes" not in c or "d2h_bytes" not in c:
        return None
    return (c["h2d_bytes"] + c["d2h_bytes"]) / c["ticks"] / 1000.0
