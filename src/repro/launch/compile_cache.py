"""Where JAX keeps its persistent compilation cache.

Call :func:`setup_compile_cache` at the start of a program's ``main``
(never at import). The path stays fixed from run to run, so a later run
finds what an earlier one stored: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads it itself, and nothing here overrides
it), else ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
