"""JAX's persistent compilation cache for the entry points that compile
for the chip.

``JAX_COMPILATION_CACHE_DIR``, when set, is the only cache location: JAX
reads it itself and nothing here overrides it. Otherwise the cache lives
at a fixed directory inside the checkout (``<repo>/.jax_cache``,
git-ignored). The path is part of what a cached program is keyed on, so
it never depends on a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that path. Call before the first compile."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
