"""Share of its roofline that the serving attention kernel reaches: the
least time it could take for the ticks of the traced window
(``kernels/ragged_paged_attention.py``: the live keys and values those
ticks must read) over the device time of the Pallas kernel in the trace,
on one chip."""

from harness import cells, trace as T
from harness.measure import say


def read(run):
    if run["peaks"] is None:
        return None
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "serve":
        return None
    seconds, calls = T.op_seconds(tr, T.is_kernel)
    if calls == 0:
        return None
    cfg = run["cell"].config
    ragged = cells.kernel("ragged_paged_attention")
    t0, t1 = run["tracing"].t0, run["tracing"].t1
    ticks = [k for k in run["ticks"] if k["t0"] >= t0 and k["t1"] <= t1]
    if not ticks:
        return None
    per = [ragged.least_seconds(k["live_kv_tokens"], k["prefill_rows"],
                                cfg["n_embd"], run["peaks"], tp=run["tp"])
           for k in ticks]
    # the trace may hold a tick more or less than the host counted: scale
    # by the kernel calls it really has (one per layer and tick)
    least = sum(p["seconds"] for p in per) / len(per) * calls
    say(f"ragged_roofline.serve: {calls} kernel calls, "
        f"{1e6 * seconds / calls:.1f} us each against a least "
        f"{1e6 * least / calls:.1f} us; bound {per[0]['bound']}")
    return 100.0 * least / seconds
