"""JAX's persistent compilation cache for the entry points.

``use_compile_cache()`` runs first in ``repro.launch.serve`` and in
``chip_smoke.py``, before anything is compiled. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
directory is left as it is. Otherwise compiled programs go to one fixed
directory inside the checkout: the cache's key includes its path, so a
directory that moved from run to run would never hit. Importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
