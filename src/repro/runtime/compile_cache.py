"""Where JAX's persistent compilation cache lives.

A later run finds what an earlier one compiled only if the directory
does not move: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), otherwise a fixed ``.jax_cache/``
at the checkout root.  Entry points call :func:`enable_compile_cache` before
their first compile; tests never do.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
