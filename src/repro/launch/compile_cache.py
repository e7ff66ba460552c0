"""Where this repository's programs keep JAX's persistent compilation cache.

The cache's key includes its directory, so the directory must not move
between runs: a temporary name, a pid or a timestamp would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — src/repro/launch/compile_cache.py is three levels
# below the checkout's root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; call before
    the first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it and nothing is set here; otherwise the cache goes to the fixed
    ``.jax_cache`` directory of the checkout.  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
