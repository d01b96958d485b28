"""Share of their roofline that the training attention kernels reach: the
least time the forward, dKV and dQ kernels could take for the steps of the
traced window (``kernels/flash_attention.py``) over the device time of the
Pallas kernels in the trace."""

from harness import cells, trace as T
from harness.measure import say


def read(run):
    if run["peaks"] is None:
        return None
    tr = run.get("trace")
    if tr is None or not tr.chips or run["kind"] != "train":
        return None
    seconds, calls = T.op_seconds(tr, T.is_kernel)
    if calls == 0:
        return None
    cfg = run["cell"].config
    flash = cells.kernel("flash_attention")
    layouts = run["layouts"]
    per_layer = [flash.least_seconds(lay, cfg["n_embd"], run["peaks"])
                 for lay in layouts]
    least_step = run["layers_run"] * sum(
        p["seconds"] for p in per_layer) / len(per_layer)
    steps = calls / (3.0 * run["layers_run"])      # three kernels a layer
    say(f"attn_roofline.train: {calls} kernel calls ({steps:.1f} steps), "
        f"{1e3 * seconds / steps:.3f} ms a step against a least "
        f"{1e3 * least_step:.3f} ms; bound {per_layer[0]['bound']}")
    return 100.0 * least_step * steps / seconds
