"""Where JAX keeps compiled programs between processes.

Entry points call :func:`use_compile_cache` first thing in ``main()``; the
module does nothing when imported.
"""
from __future__ import annotations

import os
from pathlib import Path

#: a fixed path at the root of the checkout: the directory is part of the
#: cache key, so a name that changed per run would never hit
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``.jax_cache/`` in the checkout.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
