"""Where the entry points keep JAX's persistent compilation cache.

A compiled serve step is keyed partly by the cache directory's path, so
the directory must not move between runs: it is either the one named by
`JAX_COMPILATION_CACHE_DIR` (which JAX reads by itself) or the fixed
`<repo>/.jax_cache`. Call `enable_compilation_cache()` at the start of an
entry point, never at import.
"""

from __future__ import annotations

import os

import jax

#: the repository root: src/repro/launch/ -> three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)))

#: the cache directory used when the environment names none
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With `JAX_COMPILATION_CACHE_DIR` set this changes nothing: JAX has
    already taken that directory from the environment. Otherwise the
    cache goes to `DEFAULT_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
