"""Tests only: calls of the compiled train step over the window, as a
counter that the test publishes into ``paddle_tpu.obs``'s default registry
and the train driver hands on in ``run["counters"]``."""


def read(run):
    return run["counters"].get("zz_family_steps_total")
