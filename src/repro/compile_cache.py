"""Where the persistent compilation cache lives.

A cold run of the MSF engines is mostly compilation, so every entry
point calls ``place_compile_cache()`` once, before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is changed.  Otherwise the cache goes to ``.jax_cache`` at the
root of this checkout: a fixed path, because the path is part of what
makes a later run find the entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
