"""Model FLOP/s utilisation: operations the forward and backward passes
need per step (the family's count, ``families/<family>.py:
train_step_flops``, from shapes, nothing recomputed) times the steps per
second, over chips times peak.  The rate is taken over the whole steps
that ended after the profiler had stopped (starting and stopping it costs
the host seconds), between the first and the last of them; it is the
end-to-end rate of an untraced run, times a constant."""


def read(run):
    if run["peaks"] is None or run["kind"] != "train":
        return None
    after = [s["done"] for s in run["steps"]
             if s["done"] > run["tracing"].ended]
    if len(after) < 3:
        return None
    steps_per_s = (len(after) - 1) / (after[-1] - after[0])
    cell = run["cell"]
    layouts = run["layouts"]
    per_step = sum(cell.family().train_step_flops(cell.config, lay)
                   for lay in layouts) / len(layouts)
    return 100.0 * per_step * steps_per_s / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
