"""Where JAX keeps its persistent compilation cache.

`enable_compile_cache()` is called by the command-line entry points (the
`repro.launch` CLIs and `chip_smoke.py`) before their first compile — never
at import. If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
nothing else is configured. Otherwise the cache goes to one fixed directory
of the checkout, `.jax_cache/` (git-ignored): the cache key includes the
path, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
