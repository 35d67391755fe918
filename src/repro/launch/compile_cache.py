"""Where JAX keeps its persistent compilation cache.

A cache entry's key includes the cache path, so a directory that moves
between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing here overrides it.  Otherwise the cache sits
at a fixed path inside the checkout, ``<repo>/.jax_cache`` (gitignored),
never at a name derived from a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Call before the first compile; returns the cache directory in use."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
