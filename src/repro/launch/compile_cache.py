"""JAX's persistent compilation cache, one setting for every entry point.

A 32-layer model compiles one program per prefill bucket plus the decode
step; with the cache on, a second process on the same machine reads them
back instead of compiling again.
"""

from __future__ import annotations

import os
import pathlib

# fixed, so that the cache key (which includes the path) stays stable;
# listed in .gitignore
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
    setting and is used as it is; otherwise the cache lives in
    ``.jax_cache`` at the root of the checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
