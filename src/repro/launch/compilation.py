"""Where the entry points keep JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it
stands and nothing else is configured. Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again. Called from each ``main()``, never at
import: tests and library users keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compilation() -> str:
    """Point the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
