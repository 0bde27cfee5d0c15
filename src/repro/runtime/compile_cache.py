"""Where JAX's persistent compilation cache lives.

A cache directory set from outside, in ``JAX_COMPILATION_CACHE_DIR``,
wins: JAX reads that variable itself, and nothing here names another
directory.  Otherwise the cache goes to one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (git-ignored).  The path is part of what
a later run must find again, so it is never built from a temp name, a pid
or the time.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call once at the start of an entry point, before the first compile."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
