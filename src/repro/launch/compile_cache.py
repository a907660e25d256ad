"""Where JAX keeps its persistent compilation cache.

Called at the start of every entry point that compiles for a device
(``repro.launch.train``, ``repro.launch.serve``, ``chip_smoke.py``), never
at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here. Otherwise the cache goes to the fixed directory
``<repo root>/.jax_cache``: the cache directory is part of every entry's
key, so a path that moved between runs (a temp name, a pid, a time) would
never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
