"""JAX's persistent compilation cache, placed from outside or at one path.

Entry points (``repro.launch.serve``, ``chip_smoke.py``, the ``benchmarks/``
scripts) call ``enable_compile_cache()`` once, before their first compile;
nothing calls it at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself when it
  is imported, and this module sets no directory of its own.
* unset: the cache lives at ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed — never derived from a pid, a temp name or the time —
  because the directory is where entries are found again: a cache that
  moves never hits.

Either way every executable is written, however short its compile, so a
second run of the same program loads rather than compiles.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
