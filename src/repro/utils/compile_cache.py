"""Where entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it on its own, and
nothing is set in code.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part
of what makes a later process find an entry, so it is never a temp
name, a pid or a time.

Entry points call :func:`enable_compile_cache` from their ``main``;
importing a module never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
