"""Where JAX keeps compiled programs between runs of this checkout."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: a fixed path: the cache is keyed on it, so a moving directory never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.  Call it before the
    first compile, never at import.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here; otherwise the
    cache lives in ``.jax_cache/`` at the root of the checkout."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
