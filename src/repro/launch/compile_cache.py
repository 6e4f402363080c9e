"""JAX's persistent compilation cache, placed from outside the program.

Entry points that compile the model (``chip_smoke.py``, ``python -m
repro.launch.serve`` and the two benches) call :func:`enable_compile_cache`
once before their first compile.  Tests never call it: an AOT compile for a
described chip writes entries that cannot be read back without that chip.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path inside the checkout (listed in .gitignore),
# never a temporary or per-run name, so the next run in the same checkout
# finds what this one compiled.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing else is configured.  Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
