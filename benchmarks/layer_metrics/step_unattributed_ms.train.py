"""Device time a step that ``harness/step_parts.py`` could put down to no
part: operations with no ``op_name`` or none of the program's scopes in it
(XLA's own copies).  The check on the accounting, the twin of
``tick_uncovered_ms.serve``; the ten largest are named in the log."""

from harness import step_parts


def read(run):
    return step_parts.ms_of(run, parts=(step_parts.UNATTRIBUTED,))
