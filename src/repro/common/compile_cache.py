"""Where JAX keeps its persistent compilation cache.

A later process finds a compiled program only under the same cache path,
so the path is fixed: never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/common/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiles in JAX_COMPILATION_CACHE_DIR when it is set (JAX
    reads it itself, so nothing is set here), else in <checkout>/.jax_cache.
    Call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
