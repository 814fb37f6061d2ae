"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called at the start of ``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train`` and
``benchmarks/bench_serve.py``, before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and this
  function sets no other directory.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The path is fixed, never built from a temp name, a pid
  or the time, so a later run in the same checkout finds what an earlier
  one wrote.
* ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite sets it): no cache
  at all.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on; returns its directory, or None
    when the cache is disabled."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
