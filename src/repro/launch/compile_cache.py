"""JAX's persistent compilation cache, placed where every run finds it again.

The cache key includes the cache directory, so the directory must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names
(which JAX reads itself) or ``<checkout>/.jax_cache``."""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package runs from (``src/repro/launch`` -> root)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and nothing is changed. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout. Entry points call this from ``main()``, never
    at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
