"""Where JAX keeps its persistent compilation cache.

The cache directory is part of what a later run has to find again, so it
never comes from a temporary name, a pid or the clock.  Entry points call
:func:`use_compile_cache` from their ``main`` before the first compile;
nothing here runs at import.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to ``.jax_cache``
    at the root of the checkout.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
