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
    With ``JAX_COMPILATION_CACHE_DIR`` set no directory is set in code."""
    import jax

    # What is traced is what was lowered.  JAX leaves a module's metadata
    # (op names: the named scopes; source locations) out of an entry's key
    # by default, so two programs that differ in scopes alone share an
    # entry, and the later one is handed the earlier one's executable, HLO
    # proto and names with it: a profiler trace of a checkout would then
    # show another checkout's scopes (seen with JAX 0.9.0, PERF.md s6 PR
    # 39).  The price: an edit that moves line numbers compiles anew on a
    # checkout's first run, as an edit above a kernel always did.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
