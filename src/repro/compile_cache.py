"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), else ``.jax_cache`` at the root of
the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    other cache is set here."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return CHECKOUT_CACHE
