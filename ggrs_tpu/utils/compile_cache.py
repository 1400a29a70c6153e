"""JAX's persistent compilation cache, switched on by the entry points.

Only programs that run as entry points call `enable_compile_cache()`
(chip_smoke.py, bench.py, examples/*.py, the fleet agent); importing the
package never touches JAX's configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

# fixed on purpose: the directory's path is part of every cache key, so a
# path that moved between runs (a temp name, a pid) would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to `<repo>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
