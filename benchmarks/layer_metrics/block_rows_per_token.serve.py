"""Rows a block model computes for each token it fixes: ``block_rows /
tokens_fixed`` from ``ServingMetrics`` over the window.  With ``S``
denoising passes and the committing pass over every block of ``B`` rows it
is ``S + 1`` (3.0 as the cell runs); folding the committing pass into the
next block's first pass would bring it to ``S``.  ``None`` where the
program has no such counters, or fixed no token."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or not c.get("tokens_fixed"):
        return None
    return c["block_rows"] / c["tokens_fixed"]
