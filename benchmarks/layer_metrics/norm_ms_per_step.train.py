"""Device time a step of the normalisation layers: the parts ``layer_norm``
and ``rms_norm`` of ``harness/step_parts.py``'s attribution (the node kinds
``topology.node_scope`` writes), forward, recomputed and backward.  A norm
that XLA fused into a neighbour's product counts with the neighbour (the
``shared`` table of the log says how much)."""

from harness import step_parts

KINDS = ("layer_norm", "rms_norm")


def read(run):
    return step_parts.ms_of(run, parts=KINDS) or None
