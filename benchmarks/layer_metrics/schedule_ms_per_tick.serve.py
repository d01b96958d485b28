"""Host time a tick spends deciding: deadlines, page growth and
preemption, the host tier, admission, chunk packing, drafting: the median
over the traced window's ticks of ``pt:tick.schedule``."""

from harness import program_spans as P


def read(run):
    return P.phase_ms_per_tick(run, "tick.schedule")
