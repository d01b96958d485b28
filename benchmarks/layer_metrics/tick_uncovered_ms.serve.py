"""What the phases miss of a tick: ``pt:tick`` minus the five phases
inside it (the median over the traced window's ticks).  Near zero while
every stretch of ``engine.step()`` sits in a phase."""

from harness import program_spans as P


def read(run):
    if run["kind"] != "serve":
        return None
    spans = P.of_run(run)
    return P.median_ms([
        (hi - lo) - P.seconds_inside(spans, P.TICK_PHASES, (lo, hi))
        for lo, hi in P.named(spans, P.TICK)])
