"""JAX's persistent compilation cache, placed by the entry points.

A cold compile of the fleet scan is seconds to minutes; with the cache on,
a later process that compiles the same program reads it back instead.
The cache is keyed on its directory, so the directory never moves: it is
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, and nothing else is changed), else the fixed
``<repo>/.jax_cache``, which ``.gitignore`` lists.

Entry points (``chip_smoke.py``, ``benchmarks/``) call
:func:`setup_compile_cache` before their first compile.  Importing
``repro`` never touches the cache: tests and library users keep whatever
the process already configured.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
