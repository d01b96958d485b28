"""The logits' way back to the host: ``pt:tick.wait`` (the two
``np.asarray`` calls, which wait for the step and copy its logits) minus
the device-busy time inside it, chips averaged; the median over the traced
window's ticks."""

from harness import program_spans as P


def read(run):
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    spans = P.of_run(run)
    waits = P.named(spans, "tick.wait")
    host = dict(zip(waits, P.host_seconds(tr, waits)))
    return P.median_ms([sum(host[w] for w in waits
                            if w[0] >= lo and w[1] <= hi)
                        for lo, hi in P.named(spans, P.TICK)])
