"""Model FLOP/s utilisation: operations the forward and backward passes
need per step (``kernels/gpt2_model.py``, from shapes, nothing recomputed)
times the steps per second, over chips times peak.  The rate is taken over
the whole steps that ended after the profiler had stopped (starting and
stopping it costs the host seconds), between the first and the last of
them; it is the end-to-end rate of an untraced run, times a constant."""

from harness import cells


def read(run):
    if run["peaks"] is None or run["kind"] != "train":
        return None
    after = [s["done"] for s in run["steps"]
             if s["done"] > run["tracing"].ended]
    if len(after) < 3:
        return None
    steps_per_s = (len(after) - 1) / (after[-1] - after[0])
    cfg = run["cell"].config
    model = cells.kernel("gpt2_model")
    layouts = run["layouts"]
    per_step = sum(model.train_step_flops(
        lay, cfg["n_embd"], cfg["n_inner"], run["layers_run"],
        cfg["vocab_size"]) for lay in layouts) / len(layouts)
    return 100.0 * per_step * steps_per_s / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
