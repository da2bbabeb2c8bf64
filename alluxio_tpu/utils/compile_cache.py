"""Where JAX's persistent compilation cache lives.

The cache's path is part of its key, and the machine a chip run lands
on is thrown away after each call: a cache under a temp, pid or
timestamp path never hits. So the place is decided OUTSIDE the program
(``JAX_COMPILATION_CACHE_DIR``) and, failing that, is one fixed,
git-ignored directory inside the checkout.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the fallback: the same path in every run of this checkout
FIXED_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Call before the first compile; returns the directory in use.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is set in code; otherwise the cache goes to
    :data:`FIXED_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", FIXED_CACHE_DIR)
    return FIXED_CACHE_DIR
