"""JAX's persistent compilation cache, placed once per process.

The directory is part of the cache key's lookup path, so it must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads
it itself and nothing is set here; otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored), resolved from this package's
own location — never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Call once, before the first JAX use. Returns the directory the
    process will cache compiled programs in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
