"""Where this checkout keeps JAX's persistent compilation cache.

Entry points call `use_compile_cache()` first thing in `main`; nothing
calls it on import, so tests and library users keep JAX's own setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Place the compile cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing else is set. Otherwise the cache is `.jax_cache/` at the
    checkout root (listed in .gitignore): a fixed path, so a later run
    of the same checkout finds what an earlier one compiled."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
