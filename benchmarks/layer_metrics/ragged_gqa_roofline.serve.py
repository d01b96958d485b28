"""Share of its roofline that the serving attention kernel reaches under
grouped-query attention and several query rows a slot: the least time it
could take for the ticks of the traced window
(``kernels/ragged_paged_attention_gqa.py``: the live keys and values those
ticks must read, at the K/V width) over the device time of the Pallas
kernels named ``ragged_paged_attention`` in the trace, on one chip.
``None`` for a configuration that does not say its heads under the
published names, or without such a kernel in the trace."""

from harness import cells, trace as T
from harness.measure import say

NAME = "ragged_paged_attention"
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim")


def is_ragged(o) -> bool:
    return T.is_kernel(o) and NAME in o.name


def read(run):
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr = run.get("trace")
    cfg = run["cell"].config
    if tr is None or not tr.chips or any(k not in cfg for k in KEYS):
        return None
    seconds, calls = T.op_seconds(tr, is_ragged)
    t0, t1 = run["tracing"].t0, run["tracing"].t1
    ticks = [k for k in run["ticks"] if k["t0"] >= t0 and k["t1"] <= t1]
    if calls == 0 or not ticks:
        return None
    gqa = cells.kernel("ragged_paged_attention_gqa")
    per = [gqa.least_seconds(
        k["live_kv_tokens"], k["prefill_rows"], *(cfg[key] for key in KEYS),
        run["peaks"], rows_per_slot=cfg["serve"].get("block_length", 1))
        for k in ticks]
    # the trace may hold a tick more or less than the host counted: scale
    # by the kernel calls it really has (one per layer and tick)
    least = sum(p["seconds"] for p in per) / len(per) * calls
    say(f"ragged_gqa_roofline.serve: {calls} kernel calls, "
        f"{1e6 * seconds / calls:.1f} us each against a least "
        f"{1e6 * least / calls:.1f} us; bound {per[0]['bound']}")
    return 100.0 * least / seconds
