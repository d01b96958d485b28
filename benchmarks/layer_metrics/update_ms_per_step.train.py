"""Device time a step of the optimizer's update: every operation of phase
``update`` in ``harness/step_parts.py``'s attribution (one any of whose
members lies under the scope ``opt`` of ``optimizer.py Optimizer.apply``).
XLA fuses the per-leaf rule behind the weight-gradient product it follows,
and such an operation's time is one interval: it counts here whole, and the
log says how much of the update is shared with a product, and whose."""

from harness import step_parts
from harness.measure import say


def read(run):
    parts = step_parts.of_run(run)
    if parts is None:
        return None
    total = step_parts.ms_of(run, phases=("update",))
    with_dot = {}
    for (own, other), v in parts["shared"].items():
        if own.startswith("update "):
            with_dot[other] = with_dot.get(other, 0.0) + v
    say("update_ms_per_step.train shared with a product: " + (", ".join(
        f"{k} {v:.2f} ms" for k, v in
        sorted(with_dot.items(), key=lambda kv: -kv[1])) or "none"))
    return total
