"""Host time a tick spends handing the step to the device: the nine
``jnp.asarray`` uploads and the dispatch of the compiled step: the median
over the traced window's ticks of ``pt:tick.upload``."""

from harness import program_spans as P


def read(run):
    return P.phase_ms_per_tick(run, "tick.upload")
