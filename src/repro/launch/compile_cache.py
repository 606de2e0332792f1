"""JAX's persistent compilation cache, placed where a caller can find it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache goes to the fixed
``.jax_cache/`` at the root of the checkout: the directory is part of the
cache's key, so a path made from a temp name, a pid or the time would
never hit again.  The drivers' ``main()`` and ``chip_smoke.py`` call
:func:`enable_compile_cache`; library code and tests never do, so tests
run with the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent cache uses: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on (see module docstring); returns its
    directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
