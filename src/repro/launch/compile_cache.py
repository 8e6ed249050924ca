"""Where JAX keeps its persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins: :func:`enable_compile_cache` then sets nothing. Otherwise the
cache goes to ``.jax_cache`` at the root of the checkout. The path is
fixed on purpose: it is part of what a later process looks its entries
up by, so a directory named after a temp dir, pid or time never hits.
Entry points (``chip_smoke.py``, ``launch/train.main``,
``benchmarks/run.main``) call it before anything compiles.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent cache at its directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
