"""Share of their roofline that the flash kernels reach under grouped-query
attention: the least time of the forward, dKV and dQ kernels of an
attention block and step (``kernels/flash_attention.py``) at the width the
kernels see, query heads x head_dim (16 x 256 here: K and V arrive
broadcast to the query heads), times the attention blocks (one in every
``full_attention_interval``), over the device time of the Pallas kernels
named ``flash_*`` in the traced window.  A step runs 3 such kernels an
attention block, 4 where ``train.remat`` makes the backward pass run the
forward again; the recomputation is time the kernels take and the
roofline does not count."""

from harness import cells, trace as T
from harness.measure import say


def is_flash(o) -> bool:
    return T.is_kernel(o) and "flash_" in o.name


def read(run):
    if run["peaks"] is None or run["kind"] != "train":
        return None
    tr = run.get("trace")
    if tr is None or not tr.chips:
        return None
    seconds, calls = T.op_seconds(tr, is_flash)
    if calls == 0:
        return None
    cfg = run["cell"].config
    blocks = run["layers_run"] // cfg["full_attention_interval"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    flash = cells.kernel("flash_attention")
    per_block = [flash.least_seconds(lay, width, run["peaks"])
                 for lay in run["layouts"]]
    least_step = blocks * sum(p["seconds"] for p in per_block) \
        / len(per_block)
    per = 4.0 if cfg["train"].get("remat") else 3.0
    steps = calls / (per * blocks)
    say(f"gqa_flash_roofline.train: {calls} kernel calls ({steps:.1f} "
        f"steps), {1e3 * seconds / steps:.3f} ms a step against a least "
        f"{1e3 * least_step:.3f} ms; bound {per_block[0]['bound']}")
    return 100.0 * least_step * steps / seconds
