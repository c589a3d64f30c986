"""Persistent XLA compilation cache for the entry points.

Entry points (``launch/serve.py``, ``chip_smoke.py``, ``benchmarks/run.py``)
call `enable_compile_cache` once at start-up — never at import, so library
users and tests keep JAX's own defaults.  A full-width StepFn takes tens of
seconds to compile on a TPU; with the cache on, a second process (or a
second engine in the same process whose StepFn lowers to the same HLO)
reads the executable back instead.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it at import and uses it;
  nothing here names another directory.
- unset: ``.jax_cache/`` at the repository root (listed in ``.gitignore``).
  The path is fixed — never built from a temp name, a pid or the time — so
  runs from the same checkout hit each other's entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
