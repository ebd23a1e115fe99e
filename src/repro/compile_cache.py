"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``).

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
else is chosen here. Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout (git-ignored). The directory is fixed on purpose: it is
part of what a later run has to find again, so it is never built from a
temp name, a pid or the time.

Nothing happens on import; entry points call ``enable_compile_cache()``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
