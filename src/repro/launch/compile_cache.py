"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/quickstart.py``) call :func:`enable` once before their first
compile; library code never does. A compile cache only hits when its
directory stays put, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that itself), else ``.jax_cache``
at the root of this checkout.
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
