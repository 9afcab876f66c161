"""Where JAX keeps its persistent compile cache.

A cold process compiles every program it runs; on the chip that is a
large share of a short run.  JAX's persistent cache keeps compiled
programs between processes, keyed in part by the cache's path, so the
path must not move between runs.

  * ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it
    itself and nothing is set here.
  * otherwise: ``<checkout>/.jax_cache``, one fixed path inside the
    checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: the checkout root is three levels above ``src/repro/launch``
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
