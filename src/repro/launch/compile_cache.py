"""Where the programs of this repo keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX reads the
variable itself and nothing here overrides it. Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout (git-ignored), one fixed path,
so that later processes started from the same checkout find what earlier
ones compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
