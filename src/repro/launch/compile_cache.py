"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`configure_compile_cache` from ``main()``; importing this module
changes nothing.  The cache key includes the directory, so the path is fixed:
a run that finds the cache warm reuses its executables.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "configure_compile_cache"]

#: ``.jax_cache/`` at the root of the checkout (``src/repro/launch`` → root)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Leave a ``JAX_COMPILATION_CACHE_DIR`` from the environment alone
    (JAX reads it itself); otherwise point ``jax_compilation_cache_dir`` at
    :data:`CHECKOUT_CACHE_DIR`.  Returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
