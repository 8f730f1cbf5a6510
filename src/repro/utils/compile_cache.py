"""Where compiled programs persist between processes.

A cold run of ``select()`` at deployment size spends much of its time
compiling; JAX's persistent compilation cache keeps the executables so
the next process reloads them.  The cache is keyed by the directory it
lives in, so the directory must never move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# The checkout root: src/repro/utils/compile_cache.py → three levels up.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout — a fixed path, never one built from a temp
    name, a pid or the time.  Call before the first compilation.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
