"""Where JAX's persistent compilation cache lives, for the entry points.

A cold run at χ ≈ 10⁴ compiles the whole chain walk plus the autotuner's
candidate kernels; the persistent cache lets the next process skip that.
The cache key includes its directory, so the directory must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads it itself, and nothing else is set here), otherwise
``<checkout>/.jax_cache`` (gitignored).  Entry points call
:func:`enable_compile_cache` once at start-up; importing this module
changes nothing.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get(_ENV)
    if path:
        return path
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
