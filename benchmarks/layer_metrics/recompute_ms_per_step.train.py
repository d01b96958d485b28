"""Device time a step of recomputation: the operations of phase
``recompute`` in ``harness/step_parts.py``'s attribution (JAX writes
``rematted_computation`` into the ``op_name`` of what a ``remat_scope``
runs again in the backward pass): what ``train.remat`` costs a step."""

from harness import step_parts


def read(run):
    return step_parts.ms_of(run, phases=("recompute",)) or None
