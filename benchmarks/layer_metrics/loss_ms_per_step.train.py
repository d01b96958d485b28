"""Device time a step of the loss: the loss layers' kinds
(``classification_cost``, ``next_token_cost``: they take logits) and the
trainer's reduction of the costs (``step.loss``), all phases, from
``harness/step_parts.py``.  ``lm_head_cost`` holds its own head product, so
of it only the inner scope ``head.xent`` counts; ``head.logits`` goes to
the log."""

from harness import step_parts
from harness.measure import say

KINDS = ("classification_cost", "next_token_cost", "step.loss")
FUSED = "lm_head_cost"


def read(run):
    parts = step_parts.of_run(run)
    if parts is None:
        return None
    fused = {s: sum(v for (_, p, scope), v in parts["inner"].items()
                    if p == FUSED and scope == s)
             for s in ("head.xent", "head.logits")}
    if fused["head.logits"]:
        say(f"loss_ms_per_step.train leaves out head.logits "
            f"{fused['head.logits']:.2f} ms")
    return (step_parts.ms_of(run, parts=KINDS) + fused["head.xent"]) or None
