"""Host time a step of one batch's conversion and placement: the median of
the program's span ``pt:step.feed`` (``trainer.py SGD.train``: the feeder
and ``_shard_feeds``, on the prefetch thread where there is one) over the
traced window.  Hidden under the device's step while the device is busy."""

from harness import step_parts


def read(run):
    return step_parts.span_ms_per_step(run, "step.feed")
