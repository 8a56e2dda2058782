"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache"]

#: ``<checkout>/.jax_cache``: a fixed path, so that every run of this checkout
#: finds what an earlier one compiled
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is :data:`CHECKOUT_CACHE_DIR`.
    Entry points call this at the start of ``main``, never at import: it
    changes process-wide JAX configuration.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
