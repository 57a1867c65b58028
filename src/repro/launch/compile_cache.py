"""JAX's persistent compilation cache, kept in one fixed place.

Entry points call ``enable_compile_cache()`` from ``main()``, never at
import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
cache and no other is set; otherwise the cache is ``.jax_cache`` at the root
of the checkout. The path is part of the cache key, so it never carries a
temporary name, a pid or a timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Point JAX's compilation cache at its one directory; return it."""
    path = os.environ.get(ENV_VAR) or str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
