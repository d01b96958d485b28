"""Share of their roofline that the expert layers' grouped matrix products
reach: the least time of the nine products of a layer and step
(``kernels/moe_grouped_matmul.py``: three forward, their six gradients,
nothing recomputed) for the rows the program counted on the held experts
(``moe_rows_held_total`` over ``moe_rows_total`` times the step's tokens
and experts per token), over the device time of the Pallas kernels named
``moe_gmm`` and ``moe_tgmm`` in the traced window.  A step runs 9 such
kernels a layer, 12 where ``train.remat`` makes the backward pass run the
forward three again; the recomputation and the rows of padding inside a
group's last tile are time the kernels take and the roofline does not
count."""

from harness import cells, trace as T
from harness.measure import say

NAMES = ("moe_gmm", "moe_tgmm")


def is_grouped(o) -> bool:
    return T.is_kernel(o) and any(n in o.name for n in NAMES)


def read(run):
    if run["peaks"] is None or run["kind"] != "train":
        return None
    tr = run.get("trace")
    if tr is None or not tr.chips:
        return None
    seconds, calls = T.op_seconds(tr, is_grouped)
    c = run["counters"]
    total = sum(v for k, v in c.items() if k.startswith("moe_rows_total{"))
    if calls == 0 or not total:
        return None
    held = sum(v for k, v in c.items()
               if k.startswith("moe_rows_held_total{"))
    cfg = run["cell"].config
    t = cfg["train"]
    layers = t["moe_layers"] + t["mtp_modules"]
    tokens = sum(float(sum(lay)) for lay in run["layouts"]) \
        / len(run["layouts"])
    rows = held / total * tokens * cfg["num_experts_per_tok"]
    least = cells.kernel("moe_grouped_matmul").least_seconds(
        rows, cfg["hidden_size"], cfg["moe_intermediate_size"],
        t["experts_held"][1], run["peaks"])
    steps = calls / ((12.0 if t.get("remat") else 9.0) * layers)
    say(f"moe_gmm_roofline.train: {calls} kernel calls ({steps:.1f} steps), "
        f"{rows:.0f} rows a layer, {1e3 * seconds / steps:.3f} ms a step "
        f"against a least {1e3 * least['seconds'] * layers:.3f} ms")
    return 100.0 * least["seconds"] * layers * steps / seconds
