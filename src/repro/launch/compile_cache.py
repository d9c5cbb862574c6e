"""Persistent XLA compilation cache for the entry points.

A process that compiles the control kernels for a chip pays seconds per
kernel (``control_tick`` at 2^20 rows is one of the larger programs).
The entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call :func:`enable_compile_cache` once at start
so a later process in the same checkout loads those programs instead of
compiling them again.  Library modules never call it: importing
``repro`` leaves JAX's configuration alone.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; the
  directory is left exactly as given.
* otherwise — ``<checkout>/.jax_cache``.  The path is fixed (no
  temporary name, pid or time in it) because it is part of what makes
  a later process find the entries; ``.gitignore`` lists it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/…``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Every program is cached, however quickly it
    compiled: the small admission and planning kernels otherwise fall
    under JAX's one-second floor and are compiled anew by every run."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return Path(os.environ[ENV_VAR])
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
