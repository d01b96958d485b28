"""Tokens a running slot yields a tick: ``tokens_fixed / decode_slots``
from ``ServingMetrics`` over the window (``B / (S + 1)`` under block
diffusion with ``S`` denoising passes and a committing pass a block of
``B``; a tick of a slot yields 0 or several).  ``None`` where the program
has no such counters, or fixed no token."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("tokens_fixed") \
            or not c.get("decode_slots"):
        return None
    return c["tokens_fixed"] / c["decode_slots"]
