"""JAX's persistent compilation cache for the scripts that drive a chip.

Call `enable_compile_cache()` before the first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this module
sets nothing; otherwise the cache goes to `<repo>/.jax_cache`. The path is
fixed (never a temporary name, a PID or a time): a cache directory that
moves between runs never finds its entries again.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
