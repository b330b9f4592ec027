"""Where JAX keeps its persistent compilation cache.

Entry points (chip_smoke.py, the launchers, the examples) call
`configure_compile_cache()` once, before they compile; tests never do.
The cache's path is part of its key, so it is fixed: JAX's own
JAX_COMPILATION_CACHE_DIR when the environment sets it, otherwise
`<checkout>/.jax_cache` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's compilation cache at its one directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:  # JAX reads the variable itself; set nothing else
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
