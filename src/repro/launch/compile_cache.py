"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` at start-up,
before it compiles anything.  ``JAX_COMPILATION_CACHE_DIR``, when set,
is JAX's own setting and is the only cache used.  Otherwise the cache
lives at one fixed path inside the checkout, ``<checkout>/.jax_cache``
(listed in ``.gitignore``), so a later run of the same checkout finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` or, when that is unset, at
    ``<checkout>/.jax_cache``; returns the directory used."""
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
