"""Share of their roofline that the grouped matrix products of a served
expert layer reach where the chip holds a SHARE of the layer's experts:
the least time of the three forward products of a layer and tick
(``kernels/moe_grouped_matmul.py``: gate, up, down, float32 operands) for
the rows that landed on held experts (``moe_rows_held``; the pairs on
experts held elsewhere are not computed here) and the matrices of the held
experts that took a row (``moe_live_experts``), over the device time of
the Pallas kernels named ``moe_gmm`` in the traced window.  A tick runs 3
such kernels an expert layer, so the kernels' own count gives the traced
layers and ticks, and the counters' mean a layer and tick is taken over
the steps' expert layers, which the kernels' share of the program's calls
gives (``moe_grid_tiles`` is counted once an expert layer and step).
``moe_gmm_roofline.serve`` reckons ``moe_rows_total`` over every layer run
and is right where every expert is held and every layer has experts; this
is its reading for a held share behind a dense first layer.  ``None``
where the program counts no held rows, or ran no such kernel."""

from harness import cells, trace as T
from harness.measure import say

FORWARD = ("gate", "up", "down")


def is_gmm(o) -> bool:
    return T.is_kernel(o) and "moe_gmm" in o.name


def read(run):
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr, c = run.get("trace"), run["counters"]
    if tr is None or not tr.chips or not c.get("moe_rows_held") \
            or not c.get("step_dispatches"):
        return None
    seconds, calls = T.op_seconds(tr, is_gmm)
    if calls == 0:
        return None
    cfg, peaks = run["cell"].config, run["peaks"]
    n = run["layers_run"]
    sparse = sum(t == "sparse" for t in cfg["mlp_layer_types"][:n])
    layer_ticks = float(c["step_dispatches"] * sparse)
    parts = cells.kernel("moe_grouped_matmul").counts(
        c["moe_rows_held"] / layer_ticks, cfg["hidden_size"],
        cfg["moe_intermediate_size"], c["moe_live_experts"] / layer_ticks,
        bytes_per=4)
    least = sum(max(parts[k]["flops"] / peaks["bf16_flops_per_s"],
                    parts[k]["bytes"] / peaks["hbm_bytes_per_s"])
                for k in FORWARD)
    traced = calls / float(len(FORWARD))        # layers x ticks in the trace
    say(f"moe_gmm_held_roofline.serve: {calls} kernel calls, "
        f"{c['moe_rows_held'] / layer_ticks:.0f} held rows and "
        f"{c['moe_live_experts'] / layer_ticks:.1f} experts a layer and "
        f"tick, {1e3 * seconds / traced:.3f} ms a layer against a least "
        f"{1e3 * least:.3f} ms")
    return 100.0 * least * traced / seconds
