"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
alone.  Otherwise the cache lives in one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (gitignored), so every process started
from the same checkout finds what an earlier one compiled.  The directory
never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that
    directory.  Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
