"""Where JAX keeps its persistent compilation cache.

A cache is only found again at the path it was written to, so the path
is fixed: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX
reads that variable itself, and nothing here overrides it), otherwise
`<checkout>/.jax_cache/` (git-ignored).  Entry points call
`use_compile_cache()` once at start-up; importing this module changes
nothing.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = REPO_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
