"""Host time a tick spends inside the call of the compiled step alone
(argument checks and the launch over the mesh; the device runs on after
it returns): the median over the traced window's ticks of
``pt:tick.dispatch``, which lies inside ``pt:tick.upload`` behind the
placement of the tick's input buffer, so upload minus dispatch is the
placement.  ``None`` for a program that writes no such span (a commit
before PR 32), whatever else of the tick it names."""

from harness import program_spans as P

PHASE = "tick.dispatch"


def read(run):
    if run["kind"] != "serve" or not P.named(P.of_run(run), PHASE):
        return None
    return P.phase_ms_per_tick(run, PHASE)
