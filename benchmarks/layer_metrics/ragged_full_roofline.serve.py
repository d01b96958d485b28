"""Share of its roofline that the serving attention kernel reaches in the
full layers: the least time it could take a layer and tick
(``kernels/ragged_paged_attention_window.py``: every token its sequences
have cached,
from the program's counter ``full_kv_tokens_held``, at the layer's own
query-head count) over the device time of the Pallas kernels named
``ragged_paged_attention`` that ran under the scope ``attn.full``
(``harness/program_ops.py`` tells the two kinds' calls apart by the
``op_name`` each has in the program it ran in), on one chip.  ``None``
where the program counts no such state, or ran no such kernel under that
scope."""

from harness import cells, program_ops as P
from harness.measure import say

NAME = "ragged_paged_attention"


def share(run, kind: str, counter: str):
    """The share for one kind of layer (``ragged_window_roofline.serve``
    asks it for the window layers)."""
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr, c = run.get("trace"), run["counters"]
    if tr is None or not tr.chips or not c.get(counter) \
            or not c.get("step_dispatches"):
        return None
    seconds, calls = P.kernel_seconds(
        tr, P.programs(run["tracing"].file()), "attn." + kind, NAME)
    if calls == 0:
        return None
    cfg = run["cell"].config
    n = run["layers_run"]
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in cfg["layer_types"][:n]]
    heads = {h for h, k in zip(cfg["num_attention_heads_per_layer"][:n],
                               kinds) if k == kind}
    if len(heads) != 1:
        return None
    ticks = float(c["step_dispatches"])
    t0, t1 = run["tracing"].t0, run["tracing"].t1
    rows = [k["prefill_rows"] for k in run["ticks"]
            if k["t0"] >= t0 and k["t1"] <= t1]
    least = cells.kernel("ragged_paged_attention_window").least_seconds(
        c[counter] / ticks, sum(rows) / max(1, len(rows)), heads.pop(),
        cfg["num_key_value_heads"], cfg["head_dim"], run["peaks"])
    say(f"ragged_{kind}_roofline.serve: {calls} kernel calls, "
        f"{1e6 * seconds / calls:.1f} us each against a least "
        f"{1e6 * least['seconds']:.1f} us for {c[counter] / ticks:.0f} "
        f"live tokens a layer and tick; bound {least['bound']}")
    return 100.0 * least["seconds"] * calls / seconds


def read(run):
    return share(run, "full", "full_kv_tokens_held")
