"""Where JAX's persistent compilation cache lives.

The cache path is part of every entry's key, so it must be fixed: a
directory named after a temporary name, a process id or the time would
never be hit again. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and that setting stands; otherwise the cache goes to
``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
