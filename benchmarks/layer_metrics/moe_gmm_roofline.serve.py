"""Share of their roofline that the served expert layers' grouped matrix
products reach: the least time of the three forward products of a layer
and tick (``kernels/moe_grouped_matmul.py``: gate, up, down, float32
operands) for the rows the program counted (``moe_rows_total``) and the
matrices of the experts that held a row (``moe_live_experts``), over the
device time of the Pallas kernels named ``moe_gmm`` in the traced window.
A tick runs 3 such kernels a layer.  The rows of padding inside an
expert's last tile and the matrices of an expert that took a tile with no
row are time the kernels take and the roofline does not count.  ``None``
where the program has no such counters, or ran no such kernel."""

from harness import cells, trace as T
from harness.measure import say

FORWARD = ("gate", "up", "down")


def is_gmm(o) -> bool:
    return T.is_kernel(o) and "moe_gmm" in o.name


def read(run):
    if run["peaks"] is None or run["kind"] != "serve":
        return None
    tr, c = run.get("trace"), run["counters"]
    if tr is None or not tr.chips or not c.get("moe_rows_total") \
            or not c.get("step_dispatches"):
        return None
    seconds, calls = T.op_seconds(tr, is_gmm)
    if calls == 0:
        return None
    cfg, peaks = run["cell"].config, run["peaks"]
    layer_ticks = float(c["step_dispatches"] * run["layers_run"])
    parts = cells.kernel("moe_grouped_matmul").counts(
        c["moe_rows_total"] / layer_ticks, cfg["hidden_size"],
        cfg["moe_intermediate_size"], c["moe_live_experts"] / layer_ticks,
        bytes_per=4)
    least = sum(max(parts[k]["flops"] / peaks["bf16_flops_per_s"],
                    parts[k]["bytes"] / peaks["hbm_bytes_per_s"])
                for k in FORWARD)
    traced = calls / float(len(FORWARD))        # layers x ticks in the trace
    say(f"moe_gmm_roofline.serve: {calls} kernel calls, "
        f"{c['moe_rows_total'] / layer_ticks:.0f} rows and "
        f"{c['moe_live_experts'] / layer_ticks:.1f} experts a layer and "
        f"tick, {1e3 * seconds / traced:.3f} ms a layer against a least "
        f"{1e3 * least:.3f} ms")
    return 100.0 * least * traced / seconds
