"""Where JAX's persistent compilation cache lives — decided in ONE place.

The cache directory is part of every entry's key, so a directory that
moves (a temp name, a pid, a timestamp) never hits.  Whoever runs the
program places the cache from outside with ``JAX_COMPILATION_CACHE_DIR``
(jax reads the variable itself); only when it is unset does the program
pick a directory, and then always the same one: ``.jax_cache/`` at the
root of the checkout (git-ignored).
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set nothing is set in code."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
