"""JAX's persistent compilation cache at a place the next run finds.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()    # program start, before the first compile

Entry points call this once; importing the module changes nothing.  The
cache directory is part of what a later run must match, so it is a fixed
path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, and nothing else is set), otherwise
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory
    (leaving an environment-set one alone); returns the directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
