"""Host time a tick spends filling the step's nine numpy arrays (decode
rows, prefill rows, page table): the median over the traced window's ticks
of ``pt:tick.assemble``."""

from harness import program_spans as P


def read(run):
    return P.phase_ms_per_tick(run, "tick.assemble")
