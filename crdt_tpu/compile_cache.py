"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py` calls `enable_compile_cache` before its first compile,
so a second run on the same machine reads its programs back instead
of compiling them.
Neither the package import nor the tests turn the cache on.
"""

from __future__ import annotations

import os

#: The cache's fixed home when the environment names none. JAX keys
#: entries by path among other things, so the path never varies per
#: run (no temp names, pids or timestamps).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, stays in charge (JAX reads
    it itself); otherwise the cache lives at ``<repo>/.jax_cache``.
    Every compile is cached, however quick: a cold chip call compiles
    dozens of small programs, and together they are its set-up time."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
