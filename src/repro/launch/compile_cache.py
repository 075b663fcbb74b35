"""Where the launch entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, where it is set, is used as it is: JAX reads
it itself and nothing here overrides it. Otherwise the cache is the fixed
``.jax_cache/`` directory of the checkout, so a later run of the same
program finds what an earlier one compiled. Call ``enable_compile_cache``
from a ``main()``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/launch/ -> root


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
