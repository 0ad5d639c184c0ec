"""Where JAX keeps its persistent compilation cache for this checkout.

The launchers and ``chip_smoke.py`` call ``use_compile_cache()`` once,
before they compile anything. A cache directory given from outside through
``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads that variable itself, so the
helper sets nothing. Otherwise the cache is ``.jax_cache/`` at the root of
the checkout: a fixed path, so every process of the checkout finds what an
earlier one compiled (the directory is git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
