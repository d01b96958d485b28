"""Host time a step inside the call of the compiled step alone (argument
checks and the launch; the device runs on after it returns): the median of
the program's span ``pt:step.dispatch`` (``trainer.py SGD.train``) over the
traced window."""

from harness import step_parts


def read(run):
    return step_parts.span_ms_per_step(run, "step.dispatch")
