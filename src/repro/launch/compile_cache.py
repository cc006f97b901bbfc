"""Where the launchers keep JAX's persistent compilation cache.

The cache directory is part of every entry's key, so it has to stay put
from one run to the next: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else a fixed directory inside the checkout
(``<repo>/.jax_cache``, git-ignored).
"""

from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return it.  Call before the first compile.

    ``min_compile_time`` is zeroed so that small executables (smoke
    configs, eager page scatters) are kept too: the default threshold
    skips sub-second compiles.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
