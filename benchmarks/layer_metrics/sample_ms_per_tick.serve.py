"""Host time a tick spends on the logits once they are there: chunk
bookkeeping, finite guard, argmax or accept walk, emission and the
``on_token`` callbacks, watchdog and counters: the median over the traced
window's ticks of ``pt:tick.sample``."""

from harness import program_spans as P


def read(run):
    return P.phase_ms_per_tick(run, "tick.sample")
