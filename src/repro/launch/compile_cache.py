"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` from their ``main()``; it is
never called on import or in tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it by itself and this helper sets no other directory.
Otherwise the cache goes to ``<repo>/.jax_cache``, a fixed path: the path
is part of what a later run looks up, so it is never derived from a
temporary name, a pid or the time.

Every engine jits its own step closures, so a second engine of the same
shapes compiles again unless this cache returns the first one's executable.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, including the sub-second compiles of the
    # reduced configs and of small helper programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
