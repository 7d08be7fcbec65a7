"""JAX's persistent compilation cache for the entry points.

Called by the launchers and `chip_smoke.py` before their first compile,
never at import.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at the fixed
`<repo>/.jax_cache`: the directory is part of what a run finds again, so
it is never built from a temporary name, a PID or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
