"""Where JAX keeps its persistent compilation cache.

The z21 cascade compiles one program per level shape, so a cold
process pays those compiles on every run. Entry points call
:func:`enable_compile_cache` once, before their first compile (never at
import):

- when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it as
  ``jax_compilation_cache_dir`` and this sets no other directory;
- otherwise the cache goes to ``<checkout>/.jax_cache``. The path is
  fixed (no tempdir, pid or time in it) because it is part of the cache
  key: a directory that moves between runs never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The repository checkout this package was imported from.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
